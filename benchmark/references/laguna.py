"""The plain reference of the mixed stack (``architecture: laguna``,
huggingface.co/poolside/Laguna-S-2.1): float32 ``jax.numpy`` forward, loss,
gradients and AdamW, matmul precision ``highest``, no kernel, nothing of the
program.

Equations (layer ``l``, input ``x`` [S, h]; linears without bias; every N an
RMSNorm with a learned scale):

    y = N1_l(x);  [q k v] = y Wqkv  (q [S, H_l, d], k and v [S, nkv, d];
                  H_l by the layer's attention type)
    rope: sliding layers, theta_s on all d dims.  Full layers, YaRN: on the
          first r = d x partial_rotary_factor dims, inverse frequency i =
          theta_f^(-2i/r) x ((1 - ramp_i) + ramp_i / factor), ramp the linear
          ramp between the dims that make beta_fast and beta_slow turns in
          the original context; cos and sin times attention_factor; the other
          d - r dims unrotated
    a = softmax(q k^T / sqrt(d) + mask) v per head (kv heads shared by groups
        of H_l / nkv; mask causal, in sliding layers key j visible to query i
        only if i - j < window)
    g = sigmoid(y Wg), Wg [h, H_l];  a_h <- g_h a_h;  x <- x + concat_h(a_h) Wo
    z = N2_l(x)
    dense layer:   x <- x + Wdown (silu(Wgate z) * (Wup z))
    sparse layer:  s = softmax(z Wr) over E;  T = the k largest;
                   w_e = scale x s_e / sum_T s;
                   x <- x + sum_{e in T, lo <= e < hi} w_e E_e(z) + E_shared(z)
    loss = mean CE(next token) + mean over sparse layers of
           coef x E/k x sum_e f_e P_e            (f, P over all E experts)

``[lo, hi)`` is ``num_experts_held``: the experts this chip holds.  The sum
leaves the other chips' experts out, as the deployment's chip does; the
router, its softmax and the load-balancing term run over all ``E``.

Assumed, where config.json is silent (the configuration file's ``assumed``
has each line): softmax scoring; silu; the shared expert added ungated; the
head gate reads the normed layer input ``y``; no QK-norm; aux coefficient
0.001; initializer range 0.02, gate and router initialised as every linear of
their kind.

RMSNorm, the matmuls (and the control's lower precisions), the blocked causal
attention, the chunked position-wise parts and AdamW are the accepted
reference's own functions (``benchmark/reference.py``): the same plain
``jax.numpy``, not the program.  What is this file's: the sizes, the seeded
weights under the trainer's leaf paths (one stack per kind of layer), both
rotary embeddings, the head gate, the held experts' sum, the shared expert.

Memory: a layer is recomputed in backward (only its input is kept), attention
runs in blocks of queries, position-wise parts and the head in checkpointed
chunks of tokens, so one 8192-token sequence fits beside float32 weights,
gradients and AdamW state (4 x 3.0 GiB).

``quant`` computes every matmul but the router's in a lower precision: the
*control* of the correctness check, never used by a benchmark run.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as plain

leaf_names = plain.leaf_names
TOKEN_CHUNK = plain.TOKEN_CHUNK
HIGHEST = plain.HIGHEST


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch != "laguna":
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    h, n = int(model["hidden_size"]), int(model["num_hidden_layers"])
    kinds = list(zip(model["layer_types"], model["mlp_layer_types"]))
    if len(kinds) != n:
        raise ValueError("layer_types / mlp_layer_types do not list num_hidden_layers")
    held = model.get("num_experts_held") or [0, int(model["num_experts"])]
    return {
        "h": h, "f": int(model["intermediate_size"]), "L": n, "kinds": kinds,
        "heads": {t: int(c) for t, c in model["num_attention_heads_per_layer"].items()},
        "nkv": int(model["num_key_value_heads"]), "d": int(model["head_dim"]),
        "V": int(model["vocab_size"]), "window": int(model["sliding_window"]),
        "rope": model["rope_parameters"],
        "eps": float(model.get("rms_norm_eps", 1e-6)),
        "std": float(model.get("initializer_range", 0.02)),
        "E": int(model["num_experts"]), "k": int(model["num_experts_per_tok"]),
        "lo": int(held[0]), "hi": int(held[1]),
        "fe": int(model["moe_intermediate_size"]),
        "fs": int(model.get("shared_expert_intermediate_size", 0) or 0),
        "scale": float(model.get("moe_routed_scaling_factor", 1.0)),
        "renorm": bool(model.get("norm_topk_prob", True)),
        "aux_coef": float(model.get("router_aux_loss_coef", 0.001)),
    }


def stack_name(kind) -> str:
    """The trainer's stack of a kind's layers: ``layers/<name>``."""
    return f"{kind[0].split('_')[0]}_{kind[1]}"


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths: layer ``i`` from the ``i``-th of the
    layers' keys, stacked with the layers of its kind in layer order."""
    c = dims(model)
    h, d, nkv, std = c["h"], c["d"], c["nkv"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def swiglu(ks, width):
        return {"gate_up": plain._linear(ks[4], (h, 2 * width), std),
                "down": plain._linear(ks[5], (width, h), std)}

    def layer(k, kind):
        ks = jax.random.split(k, 8)
        nh = c["heads"][kind[0]]
        p = {"input_norm": {"scale": jnp.ones((h,), jnp.float32)},
             "post_attn_norm": {"scale": jnp.ones((h,), jnp.float32)},
             "attn": {"qkv": plain._linear(ks[0], (h, (nh + 2 * nkv) * d), std),
                      "o": plain._linear(ks[3], (nh * d, h), std),
                      "gate": plain._linear(ks[6], (h, nh), std)}}
        if kind[1] == "dense":
            p["mlp"] = swiglu(ks, c["f"])
            return p
        kr, kgu, kd = jax.random.split(ks[7], 3)
        held = c["hi"] - c["lo"]
        p["mlp"] = {
            "router": {"w": jax.random.normal(kr, (h, c["E"])) * std},
            "experts": {"gate_up": jax.random.normal(kgu, (held, h, 2 * c["fe"])) * std,
                        "down": jax.random.normal(kd, (held, c["fe"], h)) * std}}
        if c["fs"]:
            p["mlp"]["shared"] = swiglu(ks, c["fs"])
        return p

    keys = jax.random.split(klayers, c["L"])
    by_kind: dict = {}
    for i, kind in enumerate(c["kinds"]):
        by_kind.setdefault(kind, []).append(layer(keys[i], kind))
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": {stack_name(kind): jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ls)
                   for kind, ls in by_kind.items()},
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": plain._linear(khead, (h, c["V"]), std),
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def rope_table(r: Mapping[str, Any], d: int, seq: int):
    """``(cos, sin, rotary dims)`` for positions ``0 .. seq - 1``: plain rope,
    or the HF ``yarn`` rule, on the first ``d x partial_rotary_factor`` dims."""
    rot = int(d * float(r.get("partial_rotary_factor", 1.0)))
    theta = float(r["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if str(r.get("rope_type", "default")) == "yarn":
        original = float(r["original_max_position_embeddings"])

        def dim_of(turns):  # the (fractional) dim whose wavelength makes ``turns`` turns
            return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(dim_of(float(r["beta_fast"]))), 0)
        high = min(math.ceil(dim_of(float(r["beta_slow"]))), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        inv = inv * ((1 - ramp) + ramp / float(r["factor"]))
        factor = float(r.get("attention_factor") or 0.1 * math.log(float(r["factor"])) + 1)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor, rot


def rotate(x, table):
    """``x [s, heads, d]``: pairs ``(x[i], x[i + rot/2])`` of the first
    ``rot`` dims rotated, the rest passed."""
    cos, sin, rot = table
    half = rot // 2
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def expert_block(lp, z, c, mm, left_out=()):
    """The sparse MLP of ``z [t, h]`` -> ``(out, aux)``: the held experts
    over every token, weighted by the gate (zero for an expert a token did not
    choose), plus the shared expert."""
    probs = jax.nn.softmax(jnp.matmul(z, lp["router"]["w"], precision=HIGHEST), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, c["k"])
    if c["renorm"] and "renorm" not in left_out:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if "scale" not in left_out:
        top_p = top_p * c["scale"]
    onehot = jax.nn.one_hot(top_i, c["E"], dtype=jnp.float32)  # [t, k, E]
    gates = jnp.einsum("tk,tke->te", top_p, onehot)[:, c["lo"]:c["hi"]]
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = c["aux_coef"] * c["E"] * jnp.sum(f_e * jnp.mean(probs, axis=0)) / c["k"]

    def swiglu(x, gate_up, down):
        gate, up = jnp.split(mm("th,hf->tf", x, gate_up), 2, axis=-1)
        return mm("tf,fh->th", jax.nn.silu(gate) * up, down)

    def chunk(args):
        zc, gc = args

        def one(acc, ew):
            return acc + swiglu(zc, ew["gate_up"], ew["down"]) * ew["g"][:, None], None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(zc),
                              {**lp["experts"], "g": gc.T})
        if "shared" in lp and "shared" not in left_out:
            acc = acc + swiglu(zc, lp["shared"]["gate_up"]["w"], lp["shared"]["down"]["w"])
        return acc

    return plain._over_chunks(chunk, (z, gates), TOKEN_CHUNK), aux


def layer_forward(lp, x, kind, tables, c, mm, rows: int, left_out=()):
    """One layer of ``kind`` on ``x [rows * s, h]`` -> ``(x, aux)``.
    ``left_out`` names parts a test leaves out to show that each is held."""
    nkv, d, eps = c["nkv"], c["d"], c["eps"]
    nh = c["heads"][kind[0]]
    s = x.shape[0] // rows
    window = c["window"] if kind[0] == "sliding_attention" and "window" not in left_out else None
    y = plain._rms(x, lp["input_norm"]["scale"], eps)
    qkv = plain._over_chunks(
        lambda yc: mm("th,hf->tf", yc, lp["attn"]["qkv"]["w"]), y, TOKEN_CHUNK)
    qh, kh, vh = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    outs = []
    for r in range(rows):
        sl = slice(r * s, (r + 1) * s)
        outs.append(plain._attention(
            rotate(qh[sl].reshape(s, nh, d), tables[kind[0]]),
            rotate(kh[sl].reshape(s, nkv, d), tables[kind[0]]),
            vh[sl].reshape(s, nkv, d), window, mm))
    att = jnp.concatenate(outs, axis=0)
    if "head_gate" not in left_out:
        gate = jax.nn.sigmoid(mm("th,hn->tn", y, lp["attn"]["gate"]["w"]))
        att = (att.reshape(-1, nh, d) * gate[:, :, None]).reshape(-1, nh * d)
    x = x + plain._over_chunks(
        lambda ac: mm("th,hf->tf", ac, lp["attn"]["o"]["w"]), att, TOKEN_CHUNK)
    z = plain._rms(x, lp["post_attn_norm"]["scale"], eps)
    if kind[1] == "dense":
        return x + plain._dense_mlp(lp["mlp"], z, mm)[0], 0.0
    out, aux = expert_block(lp["mlp"], z, c, mm, left_out)
    return x + out, aux


def tables_for(c, seq: int, left_out=()) -> dict:
    tables = {}
    for t in {kind[0] for kind in c["kinds"]}:
        r = dict(c["rope"][t])
        if "partial_rotary" in left_out:
            r["partial_rotary_factor"] = 1.0
        if "attention_factor" in left_out and r.get("rope_type") == "yarn":
            r["attention_factor"] = 1.0
        tables[t] = rope_table(r, c["d"], seq)
    return tables


def microbatch_loss(params, tokens, c, quant=None, left_out=()):
    """Loss of one micro-batch ``tokens [rows, seq]``: mean next-token cross
    entropy over its positions plus the router's load-balancing term."""
    mm = plain._matmul(quant)
    rows, s = tokens.shape
    tables = tables_for(c, s, left_out)
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    aux_total, at = 0.0, {}
    for kind in c["kinds"]:
        name = stack_name(kind)
        i = at.get(name, 0)
        at[name] = i + 1
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"][name])
        x, aux = jax.checkpoint(
            lambda lp, x, kind=kind: layer_forward(lp, x, kind, tables, c, mm, rows, left_out)
        )(lp, x)
        aux_total = aux_total + aux
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
    n_sparse = sum(1 for kind in c["kinds"] if kind[1] == "sparse")
    return jnp.sum(per_tok) / jnp.sum(valid) + aux_total / max(n_sparse, 1)


def batch_loss(params, tokens, c, quant=None):
    """``tokens [micro, rows, seq]``: the mean of the micro-batches' losses
    (gradient accumulation averages them)."""
    def one(acc, mb):
        return acc + microbatch_loss(params, mb, c, quant), None
    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), tokens)
    return total / tokens.shape[0]


# --------------------------------------------------------------------------
# the run the check compares with
# --------------------------------------------------------------------------


def build_step(model: Mapping[str, Any], optim: Mapping[str, Any],
               clip: Optional[float], *, quant: Optional[str] = None,
               place=lambda tree: tree):
    """The jitted reference step ``(params, mu, nu, tokens, step1, lr) ->
    (params, mu, nu, loss, grad_norm, leaf_norms)``; state is donated."""
    c = dims(model)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, tokens, step1, lr):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(batch_loss)(params, tokens, c, quant)
            grads = place(grads)
            params, mu, nu, gnorm, leaf_norms = plain.adamw(
                params, grads, mu, nu, step1, lr, optim, clip)
        return place(params), place(mu), place(nu), loss, gnorm, leaf_norms

    return step


def run(model: Mapping[str, Any], optim: Mapping[str, Any], clip: Optional[float],
        tokens_per_step: list, seed: int, *, quant: Optional[str] = None,
        shard=None) -> dict:
    """Train ``len(tokens_per_step)`` steps from the seeded weights on the
    given ``[micro, rows, seq]`` token arrays.  Returns host numbers only:
    per-step ``loss`` and ``grad_norm`` (before clipping), per-leaf norms of
    the first gradient as the optimizer got it (``grad1``) and of the
    parameters' change after the last step (``dparam``)."""
    place = shard or (lambda tree: tree)
    step = build_step(model, optim, clip, quant=quant, place=place)
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
