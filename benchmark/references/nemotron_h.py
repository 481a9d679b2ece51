"""The plain reference of a stack of single-mixer layers: Mamba-2 state-space
mixers, sigmoid-routed non-gated experts beside a shared expert, attention with
no position embedding (``architecture: nemotron_h``;
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): float32
``jax.numpy`` forward, loss, gradients, AdamW and the selection bias's rule,
matmul precision ``highest``, no kernel, nothing of the program.

Equations (layer ``l``, input ``x`` [S, h]; linears without bias; every N an
RMSNorm of ``layer_norm_epsilon`` with a learned scale):

    u = N_l(x);  x <- x + Mixer_l(u), the mixer by hybrid_override_pattern[l]
    M (Mamba-2; H heads of P dims, G groups of H / G heads, state N, K taps):
        [z ; xBC ; dt] = u W_in       (W_in [h, HP + (HP + 2GN) + H], that order)
        xBC_t <- silu(sum_{j < K} w[j] xBC_{t - (K - 1) + j} + b)   (zero before
              the sequence's start; w [K, HP + 2GN], one filter a channel)
        xBC -> x [H, P], B [G, N], C [G, N]
        dt_h = softplus(dt_h + dt_bias_h);  a_h = -exp(A_log_h)
        S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T   (S [P, N] a head, zero at
              the start; head h reads group h // (H / G))
        y_t = S_t C_t + D_h x_t
        y <- N_G(y * silu(z))         (the gate first, then an RMS norm inside
              each of the G groups of HP / G channels, one scale of HP)
        Mixer = y W_out               (W_out [HP, h])
    E (sparse):  s = sigmoid(u W_r) over E;  T = the k largest of s + b_l;
        w_e = scale x s_e / (sum_T s + 1e-20);  F(u) = relu(u W_up)^2 W_down
        Mixer = sum_{e in T, lo <= e < hi} w_e F_e(u) + F_shared(u)
    * (attention; Hq query and Gk key/value heads of d dims):
        [q ; k ; v] = u W_qkv;  NO rope and no other position term
        a_i = softmax(q_i . k_{i // (Hq/Gk)} / sqrt(d) + causal) v_{i // (Hq/Gk)}
        Mixer = concat_i(a_i) W_o
    logits = N_f(x) W_head                               (final_norm; untied)
    loss = mean CE(next token)                           (no auxiliary term)
    after each optimizer step:  b_l,e <- b_l,e + gamma x sign(mean_e(c_l) - c_l,e),
        c_l,e the (token, choice) slots expert e of layer l was chosen for in
        the step's tokens (all E, summed over micro-batches)

``b`` is a leaf (``.../router/bias``) with a gradient of exactly zero: AdamW
holds zero moments for it and moves it by nothing, the rule moves it.
``[lo, hi)`` is ``num_experts_held``: the experts this chip holds; the sum
leaves the other chips' experts out, the router, its scores, the selection
and the loads run over all ``E``; the shared expert is whole.

The scan is the recurrence itself: a ``lax.scan`` over the tokens that
carries ``S``, in checkpointed blocks of tokens, NOT the chunked algorithm the
program runs (``ops/ssd.py``): the two share no decomposition.

Assumed, where config.json is silent (the configuration file's ``assumed``
has each line): no position embedding in attention (the ``nemotron_h`` code
builds none); the bias's rule and gamma 0.001 (DeepSeek-V3,
arXiv:2412.19437, 2.1.2; the source keeps the bias as a buffer); no auxiliary
loss; the renormalising 1e-20; ``dt`` unclamped (``time_step_limit`` (0, inf));
the initial values (``A_log = log(1..H)``, ``D`` 1, ``dt_bias`` the inverse
softplus of a log-uniform draw in [``time_step_min``, ``time_step_max``]
floored at ``time_step_floor``, ``out_proj`` / sqrt(layers) under
``rescale_prenorm_residual``, taps drawn as the linears, their bias 0,
initializer range 0.02, norms 1, selection bias 0); the leaf names, one stack
a kind (``layers/mamba``, ``layers/moe``, ``layers/attention``).

RMSNorm, the matmuls (and the control's lower precisions), grouped attention
in blocks of queries, the chunked position-wise parts and AdamW are the
accepted reference's own functions (``benchmark/reference.py``): the same
plain ``jax.numpy``, not the program.  This file's: the sizes, the seeded
weights under the trainer's leaf paths, the Mamba-2 mixer (convolution,
recurrence, gated norm), the sigmoid router with its bias, the non-gated
experts' sum, the untied head, the rule.

Memory: a layer is recomputed in backward (only its input is kept), attention
runs in blocks of queries, the recurrence in checkpointed blocks of tokens,
position-wise parts and the head in checkpointed chunks of tokens.

``quant`` computes every matmul but the router's in a lower precision and
rounds the recurrence's operands ``x``, ``B`` and ``C`` to it (the program's
chunked algorithm reads them as matmul operands), or, ``bf16_state``, the
matmuls as they are and the recurrence's decay and carried state in bfloat16: the *controls* of the correctness check, never used by a
benchmark run.  ``left_out`` names parts a test leaves out to show that each
is held.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as plain

leaf_names = plain.leaf_names
TOKEN_CHUNK = plain.TOKEN_CHUNK
HIGHEST = plain.HIGHEST
RENORM_EPS = 1e-20
#: tokens of a checkpointed block of the recurrence
SCAN_BLOCK = 128
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch != "nemotron_h":
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    n = int(model.get("num_hidden_layers", model.get("num_layers")))
    pattern = str(model["hybrid_override_pattern"])[:n]
    if len(pattern) != n or set(pattern) - set(KINDS):
        raise ValueError(f"reference: pattern {pattern!r} for {n} layers (known: M, E, *)")
    experts = int(model.get("n_routed_experts", 0) or 0)
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    H, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N = int(model["n_groups"]), int(model["ssm_state_size"])
    return {
        "h": int(model["hidden_size"]), "L": n,
        "kinds": [KINDS[ch] for ch in pattern],
        "H": H, "P": P, "G": G, "N": N, "K": int(model.get("conv_kernel", 4)),
        "inner": H * P, "conv": H * P + 2 * G * N,
        "Hq": heads, "Gk": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "V": int(model["vocab_size"]),
        "eps": float(model.get("layer_norm_epsilon", 1e-5)),
        "std": float(model.get("initializer_range", 0.02)),
        "E": experts, "k": int(model.get("num_experts_per_tok", 1)),
        "lo": int(held[0]), "hi": int(held[1]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
        "fs": int(model.get("moe_shared_expert_intermediate_size", 0) or 0),
        "scale": float(model.get("routed_scaling_factor", 1.0)),
        "renorm": bool(model.get("norm_topk_prob", True)),
        "gamma": float(model.get("router_bias_update_rate", 0.001)),
        "dt_min": float(model.get("time_step_min", 0.001)),
        "dt_max": float(model.get("time_step_max", 0.1)),
        "dt_floor": float(model.get("time_step_floor", 1e-4)),
        "rescale": bool(model.get("rescale_prenorm_residual", True)),
    }


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths: layer ``i`` from the ``i``-th of the
    layers' keys, stacked with the layers of its kind in layer order."""
    c = dims(model)
    h, std = c["h"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def layer(k, kind):
        ks = jax.random.split(k, 8)
        p: dict = {"norm": {"scale": jnp.ones((h,), jnp.float32)}}
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(ks[2], (c["H"],)) * (
                math.log(c["dt_max"]) - math.log(c["dt_min"])) + math.log(c["dt_min"]))
            step = jnp.maximum(step, c["dt_floor"])
            out_std = std / math.sqrt(c["L"]) if c["rescale"] else std
            p["mixer"] = {
                "in_proj": plain._linear(ks[0], (h, c["inner"] + c["conv"] + c["H"]), std),
                "conv": {**plain._linear(ks[1], (c["K"], c["conv"]), std),
                         "bias": jnp.zeros((c["conv"],), jnp.float32)},
                "head_scales": {"A_log": jnp.log(jnp.arange(1, c["H"] + 1, dtype=jnp.float32)),
                                "D": jnp.ones((c["H"],), jnp.float32),
                                "dt_bias": step + jnp.log(-jnp.expm1(-step))},
                "gated_norm": {"scale": jnp.ones((c["inner"],), jnp.float32)},
                "out_proj": plain._linear(ks[3], (c["inner"], h), out_std)}
        elif kind == "attention":
            p["attn"] = {"qkv": plain._linear(ks[0], (h, (c["Hq"] + 2 * c["Gk"]) * c["d"]), std),
                         "o": plain._linear(ks[3], (c["Hq"] * c["d"], h), std)}
        else:
            kr, kup, kd = jax.random.split(ks[7], 3)
            held = c["hi"] - c["lo"]
            p["mlp"] = {
                "router": {"w": jax.random.normal(kr, (h, c["E"])) * std,
                           "bias": jnp.zeros((c["E"],), jnp.float32)},
                "experts": {"gate_up": jax.random.normal(kup, (held, h, c["fe"])) * std,
                            "down": jax.random.normal(kd, (held, c["fe"], h)) * std}}
            if c["fs"]:
                p["mlp"]["shared"] = {"gate_up": plain._linear(ks[4], (h, c["fs"]), std),
                                      "down": plain._linear(ks[5], (c["fs"], h), std)}
        return p

    keys = jax.random.split(klayers, c["L"])
    by_kind: dict = {}
    for i, kind in enumerate(c["kinds"]):
        by_kind.setdefault(kind, []).append(layer(keys[i], kind))
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": {name: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ls)
                   for name, ls in by_kind.items()},
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": plain._linear(khead, (h, c["V"]), std),
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def causal_conv(x, taps, bias, left_out=()):
    """One sequence: ``x [s, c]``, ``taps [K, c]``, ``bias [c]`` -> ``silu`` of
    the last ``K`` inputs weighed by the taps (zeros before the start) plus
    the bias."""
    k, s = taps.shape[0], x.shape[0]
    y = taps[k - 1] * x
    if "taps" not in left_out:
        for back in range(1, k):
            y = y + taps[k - 1 - back] * jnp.pad(x, ((back, 0), (0, 0)))[:s]
    if "conv_bias" not in left_out:
        y = y + bias
    return jax.nn.silu(y) if "conv_silu" not in left_out else y


def recurrence(x, b, c_, dt, a, d, state_dtype=jnp.float32):
    """One sequence: ``x [s, H, P]``, ``b`` / ``c_ [s, G, N]``, ``dt [s, H]``
    (after its softplus), ``a`` / ``d [H]`` -> ``y [s, H, P]``: the state
    ``S [H, P, N]`` carried token by token (``state_dtype``: the control's)."""
    s, H, P = x.shape
    G, N = b.shape[1:]
    block = plain._chunks(s, SCAN_BLOCK)

    def token(S, row):
        xt, bt, ct, dtt = row
        bh, ch = jnp.repeat(bt, H // G, axis=0), jnp.repeat(ct, H // G, axis=0)   # [H, N]
        decay = jnp.exp(dtt * a).astype(state_dtype)
        S = (decay[:, None, None] * S
             + ((dtt[:, None] * xt)[:, :, None] * bh[:, None, :]).astype(state_dtype))
        S = S.astype(state_dtype)
        y = jnp.sum(S.astype(jnp.float32) * ch[:, None, :], axis=-1) + d[:, None] * xt
        return S, y

    @jax.checkpoint
    def tokens(S, rows):
        return jax.lax.scan(token, S, rows)

    rows = jax.tree_util.tree_map(
        lambda t: t.reshape((s // block, block) + t.shape[1:]), (x, b, c_, dt))
    _, y = jax.lax.scan(tokens, jnp.zeros((H, P, N), state_dtype), rows)
    return y.reshape(s, H, P)


def gated_norm(y, z, scale, groups: int, eps: float, left_out=()):
    """``y, z [t, HP]``: the gate, then an RMS norm inside each group."""
    if "gate" not in left_out:
        y = y * jax.nn.silu(z)
    t, width = y.shape
    if "norm_groups" in left_out:
        groups = 1
    g = y.reshape(t, groups, width // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(t, width) * scale


def operand_rounding(quant: Optional[str]):
    """What the control does to the recurrence's operands ``x``, ``B`` and
    ``C``: the program's chunked algorithm reads them as matmul operands in
    its compute dtype, so a control in a lower precision rounds them as it
    rounds a matmul's (value only; gradients pass unrounded)."""
    if quant == "bf16":
        return lambda x: x + jax.lax.stop_gradient(
            x.astype(jnp.bfloat16).astype(jnp.float32) - x)
    if quant == "fp8":
        return lambda x: x + jax.lax.stop_gradient(
            plain._scaled_cast(x, jnp.float8_e4m3fn, 448.0) - x)
    return lambda x: x


def mamba(lp, u, c, mm, rows: int, left_out=(), state_dtype=jnp.float32,
          rounded=lambda x: x):
    """The Mamba-2 mixer on the normed ``u [rows * s, h]``, one sequence
    after another (each kept as its input and run again in backward)."""
    a = lp["mixer"]
    s = u.shape[0] // rows
    H, P, G, N, inner = c["H"], c["P"], c["G"], c["N"], c["inner"]
    scales = a["head_scales"]
    neg = -jnp.exp(scales["A_log"])
    d = scales["D"] if "skip" not in left_out else jnp.zeros_like(scales["D"])

    def sequence(us):
        proj = plain._over_chunks(lambda uc: mm("th,hf->tf", uc, a["in_proj"]["w"]), us,
                                  TOKEN_CHUNK)
        z, xbc, dt = jnp.split(proj, [inner, inner + c["conv"]], axis=-1)
        step = jax.nn.softplus(dt + scales["dt_bias"])
        mixed = causal_conv(xbc, a["conv"]["w"], a["conv"]["bias"], left_out)
        x, b, c_ = (rounded(t) for t in jnp.split(mixed, [inner, inner + G * N], axis=-1))
        y = recurrence(x.reshape(s, H, P), b.reshape(s, G, N), c_.reshape(s, G, N),
                       step, neg, d, state_dtype).reshape(s, inner)
        y = gated_norm(y, z, a["gated_norm"]["scale"], G, c["eps"], left_out)
        return plain._over_chunks(lambda yc: mm("th,hf->tf", yc, a["out_proj"]["w"]), y,
                                  TOKEN_CHUNK)

    out = jax.lax.map(jax.checkpoint(sequence), u.reshape(rows, s, u.shape[1]))
    return out.reshape(u.shape)


def attention(lp, u, c, mm, rows: int):
    """Grouped-query attention with no position term on ``u [rows * s, h]``."""
    a, Hq, Gk, d = lp["attn"], c["Hq"], c["Gk"], c["d"]
    s = u.shape[0] // rows
    qkv = plain._over_chunks(lambda uc: mm("th,hf->tf", uc, a["qkv"]["w"]), u, TOKEN_CHUNK)
    qh, kh, vh = jnp.split(qkv, [Hq * d, (Hq + Gk) * d], axis=-1)
    outs = []
    for r in range(rows):
        sl = slice(r * s, (r + 1) * s)
        outs.append(plain._attention(qh[sl].reshape(s, Hq, d), kh[sl].reshape(s, Gk, d),
                                     vh[sl].reshape(s, Gk, d), None, mm))
    att = jnp.concatenate(outs, axis=0)
    return plain._over_chunks(lambda ac: mm("th,hf->tf", ac, a["o"]["w"]), att, TOKEN_CHUNK)


def route(lp, z, c, left_out=()):
    """``(gates [t, E], chosen one-hot summed over the k choices [t, E])``."""
    s = jax.nn.sigmoid(jnp.matmul(z, lp["router"]["w"], precision=HIGHEST))
    bias = 0.0 if "bias" in left_out else lp["router"]["bias"]
    _, top_i = jax.lax.top_k(jax.lax.stop_gradient(s + bias), c["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if c["renorm"] and "renorm" not in left_out:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + RENORM_EPS)
    top_s = top_s * c["scale"]
    onehot = jax.nn.one_hot(top_i, c["E"], dtype=jnp.float32)  # [t, k, E]
    return jnp.einsum("tk,tke->te", top_s, onehot), jnp.sum(onehot, axis=1)


def relu2_mlp(x, up, down, mm):
    return mm("tf,fh->th", jnp.square(jax.nn.relu(mm("th,hf->tf", x, up))), down)


def expert_block(lp, z, c, mm, left_out=(), held=None):
    """The sparse mixer of ``z [t, h]`` -> ``(out, loads [E])``: the held
    experts (``held``: another range than the configuration's, for a test)
    over every token, weighted by the gate (zero for an expert a token did not
    choose), and the shared expert."""
    lo, hi = held or (c["lo"], c["hi"])
    gates, chosen = route(lp, z, c, left_out)
    gates = gates[:, lo:hi]

    def chunk(args):
        zc, gc = args

        def one(acc, ew):
            return acc + relu2_mlp(zc, ew["gate_up"], ew["down"], mm) * ew["g"][:, None], None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(zc),
                              {**lp["experts"], "g": gc.T})
        if "shared" in lp and "shared" not in left_out:
            acc = acc + relu2_mlp(zc, lp["shared"]["gate_up"]["w"],
                                  lp["shared"]["down"]["w"], mm)
        return acc

    out = plain._over_chunks(chunk, (z, gates), TOKEN_CHUNK)
    return out, jax.lax.stop_gradient(jnp.sum(chosen, axis=0))


def layer_forward(lp, x, kind, c, mm, rows: int, left_out=(), held=None,
                  state_dtype=jnp.float32, rounded=lambda x: x):
    """One layer of ``kind`` on ``x [rows * s, h]`` -> ``(x, loads [E])``."""
    u = plain._rms(x, lp["norm"]["scale"], c["eps"])
    none = jnp.zeros((max(c["E"], 1),))
    if kind == "mamba":
        return x + mamba(lp, u, c, mm, rows, left_out, state_dtype, rounded), none
    if kind == "attention":
        return x + attention(lp, u, c, mm, rows), none
    out, loads = expert_block(lp["mlp"], u, c, mm, left_out, held)
    return x + out, loads


def microbatch_loss(params, tokens, c, quant=None, left_out=()):
    """``(loss, loads [the sparse layers, E])`` of one micro-batch ``tokens
    [rows, seq]``: mean next-token cross entropy over its positions."""
    state_dtype = jnp.bfloat16 if quant == "bf16_state" else jnp.float32
    mm = plain._matmul(None if quant == "bf16_state" else quant)
    rounded = operand_rounding(quant)
    rows, s = tokens.shape
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    at: dict = {}
    loads = []
    for kind in c["kinds"]:
        i = at.get(kind, 0)
        at[kind] = i + 1
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"][kind])
        x, load = jax.checkpoint(
            lambda lp, x, kind=kind: layer_forward(lp, x, kind, c, mm, rows, left_out,
                                                   state_dtype=state_dtype,
                                                   rounded=rounded))(lp, x)
        if kind == "moe":
            loads.append(load)
    y = plain._rms(x, params["final_norm"]["scale"], c["eps"])
    # next-token targets: the last position of each row predicts nothing
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((rows, 1), tokens.dtype)], axis=1).reshape(-1)
    valid = jnp.concatenate(
        [jnp.ones((rows, s - 1), jnp.float32), jnp.zeros((rows, 1), jnp.float32)],
        axis=1).reshape(-1)

    def ce(args):
        yc, tc, vc = args
        logits = mm("th,hv->tv", yc, params["lm_head"]["w"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return (lse - picked) * vc

    per_tok = plain._over_chunks(ce, (y, targets, valid), TOKEN_CHUNK)
    return (jnp.sum(per_tok) / jnp.sum(valid),
            jnp.stack(loads) if loads else jnp.zeros((0, max(c["E"], 1))))


def batch_loss(params, tokens, c, quant=None, left_out=()):
    """``tokens [micro, rows, seq]`` -> ``(the mean of the micro-batches'
    losses, their loads summed)``."""
    def one(mb):
        return microbatch_loss(params, mb, c, quant, left_out)
    losses, loads = jax.lax.map(one, tokens)
    return jnp.mean(losses), jnp.sum(loads, axis=0)


def move_bias(params, loads, gamma: float):
    """The selection bias after a step: up by ``gamma`` where an expert met
    fewer slots than the mean, down where more."""
    if "moe" not in params["layers"]:
        return params
    stack = params["layers"]["moe"]
    router = stack["mlp"]["router"]
    step = gamma * jnp.sign(jnp.mean(loads, axis=-1, keepdims=True) - loads)
    return {**params, "layers": {**params["layers"], "moe": {
        **stack, "mlp": {**stack["mlp"], "router": {**router, "bias": router["bias"] + step}}}}}


# --------------------------------------------------------------------------
# the run the check compares with
# --------------------------------------------------------------------------


def build_step(model: Mapping[str, Any], optim: Mapping[str, Any],
               clip: Optional[float], *, quant: Optional[str] = None,
               place=lambda tree: tree, left_out=()):
    """The jitted reference step ``(params, mu, nu, tokens, step1, lr) ->
    (params, mu, nu, loss, grad_norm, leaf_norms)``; state is donated."""
    c = dims(model)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, tokens, step1, lr):
        with jax.default_matmul_precision("highest"):
            (loss, loads), grads = jax.value_and_grad(batch_loss, has_aux=True)(
                params, tokens, c, quant, left_out)
            grads = place(grads)
            params, mu, nu, gnorm, leaf_norms = plain.adamw(
                params, grads, mu, nu, step1, lr, optim, clip)
            if "bias_update" not in left_out:
                params = move_bias(params, loads, c["gamma"])
        return place(params), place(mu), place(nu), loss, gnorm, leaf_norms

    return step


@functools.lru_cache(maxsize=8)
def _unplaced(model_json: str, optim_json: str, clip, quant, left_out):
    """``(step, init, zeros, change)`` on one device, kept by what they were
    built from: a seed is an argument of the compiled programs, so one set
    serves every seed of a configuration."""
    model = json.loads(model_json)
    step = build_step(model, json.loads(optim_json), clip, quant=quant, left_out=left_out)
    return (step, *_state_functions(model, lambda tree: tree))


def _state_functions(model, place):
    init = jax.jit(lambda k: place(init_params(model, k)))
    zeros = jax.jit(lambda p: place(jax.tree_util.tree_map(jnp.zeros_like, p)))

    @jax.jit
    def change(params, key):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
            params, place(init_params(model, key)))

    return init, zeros, change


def run(model: Mapping[str, Any], optim: Mapping[str, Any], clip: Optional[float],
        tokens_per_step: list, seed: int, *, quant: Optional[str] = None,
        shard=None, left_out=()) -> dict:
    """Train ``len(tokens_per_step)`` steps from the seeded weights on the
    given ``[micro, rows, seq]`` token arrays.  Returns host numbers only:
    per-step ``loss`` and ``grad_norm`` (before clipping), per-leaf norms of
    the first gradient as the optimizer got it (``grad1``) and of the
    parameters' change after the last step (``dparam``)."""
    if shard is None:
        step, init, zeros, change = _unplaced(
            json.dumps(model, sort_keys=True), json.dumps(optim, sort_keys=True), clip,
            quant, tuple(left_out))
    else:
        step = build_step(model, optim, clip, quant=quant, place=shard, left_out=left_out)
        init, zeros, change = _state_functions(model, shard)
    with jax.default_matmul_precision("highest"):
        key = jax.random.PRNGKey(int(seed))
        params = init(key)
        mu, nu = zeros(params), zeros(params)
        names = leaf_names(params)
        out: dict[str, Any] = {"loss": [], "grad_norm": []}
        for i, tokens in enumerate(tokens_per_step):
            lr = plain.learning_rate(optim, i)
            params, mu, nu, loss, gnorm, leaf_norms = step(
                params, mu, nu, jnp.asarray(tokens, jnp.int32),
                jnp.float32(i + 1), jnp.float32(lr))
            out["loss"].append(float(loss))
            out["grad_norm"].append(float(gnorm))
            if i == 0:
                out["grad1"] = dict(zip(names, (
                    float(x) for x in jax.tree_util.tree_leaves(leaf_norms))))
        del mu, nu
        out["dparam"] = dict(zip(names, (
            float(x) for x in jax.tree_util.tree_leaves(change(params, key)))))
        del params
    return out
