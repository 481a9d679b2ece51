"""The plain reference of a gated-short-convolution / attention stack with
sigmoid-routed experts (``architecture: lfm2``, ``model_type: lfm2_moe``;
huggingface.co/LiquidAI/LFM2-24B-A2B): float32 ``jax.numpy`` forward, loss,
gradients, AdamW and the selection bias's rule, matmul precision ``highest``,
no kernel, nothing of the program.

Equations (layer ``l``, input ``x`` [S, h]; linears without bias; every N an
RMSNorm of ``norm_eps`` with a learned scale; H query and G key/value heads of
``d = h / H`` dims; K = ``conv_L_cache`` taps):

    u = N_op,l(x)                                            (operator_norm)
    layer_types[l] == conv:
        [B ; C ; z] = u W_in           (W_in [h, 3h], thirds in that order)
        g_t = B_t * z_t
        c_t = sum_{j < K} w[j] * g_{t - (K - 1) + j}         (g zero before the
              sequence's start; w [K, h], one filter a channel, no bias)
        x <- x + (C * c) W_out                               (W_out [h, h])
    layer_types[l] == full_attention:
        [q ; k ; v] = u W_qkv, heads of d;  q_i <- N_q(q_i), k_j <- N_k(k_j)
              (RMS over the d dims of every head; ONE learned scale of d for
              all query heads, one for all key heads)
        rope (rope_theta, all d dims, pairs (i, i + d/2)) on q and k
        a_i = softmax(q_i . k_{i // (H/G)} / sqrt(d) + causal) v_{i // (H/G)}
        x <- x + concat_i(a_i) W_o
    y = N_ffn,l(x)                                           (ffn_norm)
    l < num_dense_layers:  x <- x + W_down (silu(W_gate y) * (W_up y))
    the others:  s = sigmoid(y W_r) over E;  T = the k largest of s + b_l;
                 w_e = scale x s_e / (sum_T s + 1e-6);
                 x <- x + sum_{e in T, lo <= e < hi} w_e E_e(y)
    logits = N_emb(x) Emb^T                    (embedding_norm; the head is tied)
    loss = mean CE(next token)                                (no auxiliary term)
    after each optimizer step:  b_l,e <- b_l,e + gamma x sign(mean_e(c_l) - c_l,e),
                 c_l,e the (token, choice) slots expert e of layer l was chosen
                 for in the step's tokens (all E, summed over micro-batches)

``b`` is a leaf (``.../router/bias``) with a gradient of exactly zero: AdamW
holds zero moments for it and moves it by nothing, the rule moves it.
``[lo, hi)`` is ``num_experts_held``: the experts this chip holds; the sum
leaves the other chips' experts out, the router, its scores, the selection
and the loads run over all ``E``.  No activation function in the convolution
operator, no shared expert.

Assumed, where config.json is silent (the configuration file's ``assumed``
has each line): the bias's rule and gamma 0.001 (DeepSeek-V3,
arXiv:2412.19437, 2.1.2; the source keeps the bias as a buffer and publishes
no rule); no auxiliary loss; the renormalising 1e-6 and the zero history of
the convolution (the source's code); the tied head; initializer range 0.02,
norms 1, bias 0, taps drawn as the linears; the leaf names, a layer's kind
``(operator, ffn)`` one stack (``layers/conv_dense``, ``layers/full_sparse``,
``layers/conv_sparse``).

RMSNorm, the rope, the matmuls (and the control's lower precisions), grouped
attention in blocks of queries, the dense MLP, the chunked position-wise parts
and AdamW are the accepted reference's own functions (``benchmark/reference.py``):
the same plain ``jax.numpy``, not the program.  This file's: the sizes, the
seeded weights under the trainer's leaf paths, the convolution operator (three
shifted adds), the head norms, the sigmoid router with its bias, the held
experts' sum, the tied head, the rule.

Memory: a layer is recomputed in backward (only its input is kept), attention
runs in blocks of queries, position-wise parts and the head in checkpointed
chunks of tokens.

``quant`` computes every matmul but the router's in a lower precision: the
*control* of the correctness check, never used by a benchmark run.
``left_out`` names parts a test leaves out to show that each is held.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as plain

leaf_names = plain.leaf_names
TOKEN_CHUNK = plain.TOKEN_CHUNK
HIGHEST = plain.HIGHEST
RENORM_EPS = 1e-6


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch not in ("lfm2", "lfm2_moe"):
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    n = int(model.get("num_hidden_layers", model.get("num_layers")))
    experts = int(model.get("num_experts", 0) or 0)
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    rope = dict(model.get("rope_parameters") or {})
    types = list(model.get("layer_types") or ["conv"] * n)[:n]
    return {
        "h": int(model["hidden_size"]), "f": int(model["intermediate_size"]),
        "L": n, "types": types,
        "dense": min(int(model.get("num_dense_layers", 2)), n) if experts else n,
        "H": heads, "G": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "K": int(model.get("conv_L_cache", 3)),
        "V": int(model["vocab_size"]),
        "theta": float(rope.get("rope_theta", model.get("rope_theta", 1e6))),
        "eps": float(model.get("norm_eps", model.get("rms_norm_eps", 1e-5))),
        "std": float(model.get("initializer_range", 0.02)),
        "E": experts, "k": int(model.get("num_experts_per_tok", 1)),
        "lo": int(held[0]), "hi": int(held[1]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
        "scale": float(model.get("routed_scaling_factor", 1.0)),
        "renorm": bool(model.get("norm_topk_prob", True)),
        "gamma": float(model.get("router_bias_update_rate", 0.001)),
    }


def kinds(c) -> list:
    """``(operator, ffn)`` of every layer; the dense layers lead."""
    return [(t, "dense" if i < c["dense"] else "sparse") for i, t in enumerate(c["types"])]


def stack_of(kind) -> str:
    """The stack a layer of this kind lies in: ``layers/<name>``."""
    return f"{kind[0].split('_')[0]}_{kind[1]}"


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths: layer ``i`` from the ``i``-th of the
    layers' keys, stacked with the layers of its kind in layer order."""
    c = dims(model)
    h, H, G, d, std = c["h"], c["H"], c["G"], c["d"], c["std"]
    kemb, klayers, _ = jax.random.split(key, 3)

    def layer(k, kind):
        ks = jax.random.split(k, 8)
        p: dict = {"operator_norm": {"scale": jnp.ones((h,), jnp.float32)},
                   "ffn_norm": {"scale": jnp.ones((h,), jnp.float32)}}
        if kind[0] == "conv":
            p["conv"] = {"in_proj": plain._linear(ks[0], (h, 3 * h), std),
                         "taps": plain._linear(ks[1], (c["K"], h), std),
                         "out_proj": plain._linear(ks[3], (h, h), std)}
        else:
            p["attn"] = {"qkv": plain._linear(ks[0], (h, (H + 2 * G) * d), std),
                         "q_norm": {"scale": jnp.ones((d,), jnp.float32)},
                         "k_norm": {"scale": jnp.ones((d,), jnp.float32)},
                         "o": plain._linear(ks[3], (H * d, h), std)}
        if kind[1] == "dense":
            p["mlp"] = {"gate_up": plain._linear(ks[4], (h, 2 * c["f"]), std),
                        "down": plain._linear(ks[5], (c["f"], h), std)}
            return p
        kr, kgu, kd = jax.random.split(ks[7], 3)
        held = c["hi"] - c["lo"]
        p["mlp"] = {
            "router": {"w": jax.random.normal(kr, (h, c["E"])) * std,
                       "bias": jnp.zeros((c["E"],), jnp.float32)},
            "experts": {"gate_up": jax.random.normal(kgu, (held, h, 2 * c["fe"])) * std,
                        "down": jax.random.normal(kd, (held, c["fe"], h)) * std}}
        return p

    keys = jax.random.split(klayers, c["L"])
    by_kind: dict = {}
    for i, kind in enumerate(kinds(c)):
        by_kind.setdefault(stack_of(kind), []).append(layer(keys[i], kind))
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": {name: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ls)
                   for name, ls in by_kind.items()},
        "embedding_norm": {"scale": jnp.ones((h,), jnp.float32)},
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def short_conv(bcz, taps, left_out=()):
    """One sequence: ``bcz [s, 3h]`` (B ; C ; z), ``taps [K, h]`` -> ``[s, h]``:
    the gate product, its last ``K`` values weighed by the taps (zeros before
    the sequence's start), the second gate."""
    gate_in, gate_out, z = jnp.split(bcz, 3, axis=-1)
    g = gate_in * z if "in_gate" not in left_out else z
    k, s = taps.shape[0], bcz.shape[0]
    c = taps[k - 1] * g
    if "taps" not in left_out:
        for back in range(1, k):
            # g_{t - back}, zeros before the start
            c = c + taps[k - 1 - back] * jnp.pad(g, ((back, 0), (0, 0)))[:s]
    return gate_out * c if "out_gate" not in left_out else c


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


def expert_block(lp, z, c, mm, left_out=(), held=None):
    """The sparse MLP of ``z [t, h]`` -> ``(out, loads [E])``: the held experts
    (``held``: another range than the configuration's, for a test) over every
    token, weighted by the gate (zero for an expert a token did not choose)."""
    lo, hi = held or (c["lo"], c["hi"])
    gates, chosen = route(lp, z, c, left_out)
    gates = gates[:, lo:hi]

    def swiglu(x, gate_up, down):
        gate, up = jnp.split(mm("th,hf->tf", x, gate_up), 2, axis=-1)
        return mm("tf,fh->th", jax.nn.silu(gate) * up, down)

    def chunk(args):
        zc, gc = args

        def one(acc, ew):
            return acc + swiglu(zc, ew["gate_up"], ew["down"]) * ew["g"][:, None], None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(zc),
                              {**lp["experts"], "g": gc.T})
        return acc

    out = plain._over_chunks(chunk, (z, gates), TOKEN_CHUNK)
    return out, jax.lax.stop_gradient(jnp.sum(chosen, axis=0))


def operator(lp, u, kind, c, mm, rows: int, left_out=()):
    """The token-mixing half of one layer on the normed ``u [rows * s, h]``."""
    s = u.shape[0] // rows
    if kind[0] == "conv":
        a = lp["conv"]
        bcz = plain._over_chunks(
            lambda uc: mm("th,hf->tf", uc, a["in_proj"]["w"]), u, TOKEN_CHUNK)
        y = jnp.concatenate([short_conv(bcz[r * s:(r + 1) * s], a["taps"]["w"], left_out)
                             for r in range(rows)], axis=0)
        return plain._over_chunks(
            lambda yc: mm("th,hf->tf", yc, a["out_proj"]["w"]), y, TOKEN_CHUNK)
    a, H, G, d = lp["attn"], c["H"], c["G"], c["d"]
    qkv = plain._over_chunks(lambda uc: mm("th,hf->tf", uc, a["qkv"]["w"]), u, TOKEN_CHUNK)
    qh, kh, vh = jnp.split(qkv, [H * d, (H + G) * d], axis=-1)
    pos = jnp.arange(s)
    outs = []
    for r in range(rows):
        sl = slice(r * s, (r + 1) * s)
        q, k = qh[sl].reshape(s, H, d), kh[sl].reshape(s, G, d)
        if "qk_norm" not in left_out:
            q = plain._rms(q, a["q_norm"]["scale"], c["eps"])
            k = plain._rms(k, a["k_norm"]["scale"], c["eps"])
        if "rope" not in left_out:
            q, k = plain._rope(q, pos, c["theta"]), plain._rope(k, pos, c["theta"])
        outs.append(plain._attention(q, k, vh[sl].reshape(s, G, d), None, mm))
    att = jnp.concatenate(outs, axis=0)
    return plain._over_chunks(lambda ac: mm("th,hf->tf", ac, a["o"]["w"]), att, TOKEN_CHUNK)


def layer_forward(lp, x, kind, c, mm, rows: int, left_out=(), held=None):
    """One layer of ``kind`` on ``x [rows * s, h]`` -> ``(x, loads [E])``."""
    u = plain._rms(x, lp["operator_norm"]["scale"], c["eps"])
    x = x + operator(lp, u, kind, c, mm, rows, left_out)
    y = plain._rms(x, lp["ffn_norm"]["scale"], c["eps"])
    if kind[1] == "dense":
        return x + plain._dense_mlp(lp["mlp"], y, mm)[0], jnp.zeros((max(c["E"], 1),))
    out, loads = expert_block(lp["mlp"], y, c, mm, left_out, held)
    return x + out, loads


def microbatch_loss(params, tokens, c, quant=None, left_out=()):
    """``(loss, {sparse stack: loads [its layers, E]})`` of one micro-batch
    ``tokens [rows, seq]``: mean next-token cross entropy over its positions."""
    mm = plain._matmul(quant)
    rows, s = tokens.shape
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    at: dict = {}
    loads: dict = {}
    for kind in kinds(c):
        name = stack_of(kind)
        i = at.get(name, 0)
        at[name] = i + 1
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"][name])
        x, load = jax.checkpoint(
            lambda lp, x, kind=kind: layer_forward(lp, x, kind, c, mm, rows, left_out))(lp, x)
        if kind[1] == "sparse":
            loads.setdefault(name, []).append(load)
    y = plain._rms(x, params["embedding_norm"]["scale"], c["eps"])
    # next-token targets: the last position of each row predicts nothing
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((rows, 1), tokens.dtype)], axis=1).reshape(-1)
    valid = jnp.concatenate(
        [jnp.ones((rows, s - 1), jnp.float32), jnp.zeros((rows, 1), jnp.float32)],
        axis=1).reshape(-1)

    def ce(args):
        yc, tc, vc = args
        logits = mm("th,vh->tv", yc, params["embed"]["embedding"])   # the tied head
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return (lse - picked) * vc

    per_tok = plain._over_chunks(ce, (y, targets, valid), TOKEN_CHUNK)
    return (jnp.sum(per_tok) / jnp.sum(valid),
            {name: jnp.stack(ls) for name, ls in loads.items()})


def batch_loss(params, tokens, c, quant=None, left_out=()):
    """``tokens [micro, rows, seq]`` -> ``(the mean of the micro-batches'
    losses, their loads summed)``."""
    def one(mb):
        return microbatch_loss(params, mb, c, quant, left_out)
    losses, loads = jax.lax.map(one, tokens)
    return jnp.mean(losses), jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), loads)


def move_bias(params, loads, gamma: float):
    """The selection bias after a step: up by ``gamma`` where an expert met
    fewer slots than the mean, down where more."""
    layers = dict(params["layers"])
    for name, load in loads.items():
        stack = layers[name]
        router = stack["mlp"]["router"]
        step = gamma * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
        layers[name] = {**stack, "mlp": {**stack["mlp"], "router": {
            **router, "bias": router["bias"] + step}}}
    return {**params, "layers": layers}


# --------------------------------------------------------------------------
# the run the check compares with
# --------------------------------------------------------------------------


def build_step(model: Mapping[str, Any], optim: Mapping[str, Any],
               clip: Optional[float], *, quant: Optional[str] = None,
               place=lambda tree: tree, left_out=()):
    """The jitted reference step ``(params, mu, nu, tokens, step1, lr) ->
    (params, mu, nu, loss, grad_norm, leaf_norms)``; state is donated."""
    c = dims(model)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
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


def run(model: Mapping[str, Any], optim: Mapping[str, Any], clip: Optional[float],
        tokens_per_step: list, seed: int, *, quant: Optional[str] = None,
        shard=None, left_out=()) -> dict:
    """Train ``len(tokens_per_step)`` steps from the seeded weights on the
    given ``[micro, rows, seq]`` token arrays.  Returns host numbers only:
    per-step ``loss`` and ``grad_norm`` (before clipping), per-leaf norms of
    the first gradient as the optimizer got it (``grad1``) and of the
    parameters' change after the last step (``dparam``)."""
    place = shard or (lambda tree: tree)
    step = build_step(model, optim, clip, quant=quant, place=place, left_out=left_out)
    with jax.default_matmul_precision("highest"):
        key = jax.random.PRNGKey(int(seed))
        params = jax.jit(lambda k: place(init_params(model, k)))(key)
        zeros = jax.jit(lambda p: place(jax.tree_util.tree_map(jnp.zeros_like, p)))
        mu, nu = zeros(params), zeros(params)

        @jax.jit
        def change(params, key):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                params, place(init_params(model, key)))

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
