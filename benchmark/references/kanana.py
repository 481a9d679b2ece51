"""The plain reference of latent attention with sigmoid-routed experts
(``architecture: kanana``, ``model_type: deepseek_v3``;
huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601): float32
``jax.numpy`` forward, loss, gradients, AdamW and the selection bias's rule,
matmul precision ``highest``, no kernel, nothing of the program.

Equations (layer ``l``, input ``x`` [S, h]; linears without bias; every N an
RMSNorm with a learned scale; H heads; dn, dr, dv = ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``; r = ``kv_lora_rank``):

    y = N1_l(x);  q = y Wq, per head [q_nope (dn) ; q_pe (dr)]
    [c (r) ; k_pe (dr)] = y Wkva          (ONE k_pe a token, for every head)
    c' = N_r(c);  c' Wkvb per head [k_nope (dn) ; v (dv)]
    rope on q_pe of each head and on k_pe: pairs (2i, 2i + 1) of the dr dims
          turned by position x theta^(-2i/dr)          (rope_interleave)
    a_h = softmax([q_nope ; q_pe]_h . [k_nope_h ; k_pe] / sqrt(dn + dr) + causal) v_h
    x <- x + concat_h(a_h) Wo
    z = N2_l(x)
    layers < first_k_dense_replace:  x <- x + Wdown (silu(Wgate z) * (Wup z))
    the others:  s = sigmoid(z Wr) over E;  T = the k largest of s + b_l;
                 w_e = scale x s_e / (sum_T s + 1e-20);
                 x <- x + sum_{e in T, lo <= e < hi} w_e E_e(z) + E_shared(z)
    loss = mean CE(next token)                                (no auxiliary term)
    after each optimizer step:  b_l,e <- b_l,e + gamma x sign(mean_e(c_l) - c_l,e),
                 c_l,e the (token, choice) slots expert e of layer l was chosen
                 for in the step's tokens (all E, summed over micro-batches)

``b`` is a leaf (``.../router/bias``) with a gradient of exactly zero: AdamW
holds zero moments for it and moves it by nothing, the rule moves it.
``[lo, hi)`` is ``num_experts_held``: the experts this chip holds; the sum
leaves the other chips' experts out, the router, its scores, the selection
and the loads run over all ``E``.

Assumed, where config.json is silent (the configuration file's ``assumed``
has each line): gamma 0.001 and the rule itself (DeepSeek-V3, arXiv:2412.19437,
2.1.2, which ``topk_method: noaux_tc`` names); no auxiliary loss; the
``n_shared_experts`` shared experts as one ungated SwiGLU of their summed
width; initializer range 0.02, norms 1, bias 0; the latent norm's leaf name.

RMSNorm, the matmuls (and the control's lower precisions), the chunked
position-wise parts and AdamW are the accepted reference's own functions
(``benchmark/reference.py``): the same plain ``jax.numpy``, not the program.
This file's: the sizes, the seeded weights under the trainer's leaf paths,
the latent attention in blocks of queries, the interleaved rope, the sigmoid
router with its bias, the held experts' sum, the shared expert, the rule.

Memory: a layer is recomputed in backward (only its input is kept), attention
runs in blocks of queries, position-wise parts and the head in checkpointed
chunks of tokens.

``quant`` computes every matmul but the router's in a lower precision: the
*control* of the correctness check, never used by a benchmark run.
``left_out`` names parts a test leaves out to show that each is held.
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
QUERY_BLOCK = plain.QUERY_BLOCK


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch not in ("kanana", "deepseek_v3"):
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    n = int(model.get("num_hidden_layers", model.get("num_layers")))
    experts = int(model.get("n_routed_experts", 0) or 0)
    dense = min(int(model.get("first_k_dense_replace", 1)), n) if experts else n
    held = model.get("num_experts_held") or [0, experts]
    return {
        "h": int(model["hidden_size"]), "f": int(model["intermediate_size"]),
        "L": n, "dense": dense, "H": int(model["num_attention_heads"]),
        "dn": int(model["qk_nope_head_dim"]), "dr": int(model["qk_rope_head_dim"]),
        "dv": int(model["v_head_dim"]), "r": int(model["kv_lora_rank"]),
        "V": int(model["vocab_size"]), "theta": float(model.get("rope_theta", 1e4)),
        "eps": float(model.get("rms_norm_eps", 1e-6)),
        "std": float(model.get("initializer_range", 0.02)),
        "E": experts, "k": int(model.get("num_experts_per_tok", 1)),
        "lo": int(held[0]), "hi": int(held[1]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
        "fs": int(model.get("n_shared_experts", 0) or 0)
        * int(model.get("moe_intermediate_size", 0) or 0),
        "scale": float(model.get("routed_scaling_factor", 1.0)),
        "renorm": bool(model.get("norm_topk_prob", True)),
        "gamma": float(model.get("router_bias_update_rate", 0.001)),
    }


def kinds(c) -> list:
    return ["dense"] * c["dense"] + ["sparse"] * (c["L"] - c["dense"])


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths: layer ``i`` from the ``i``-th of the
    layers' keys, stacked with the layers of its kind in layer order."""
    c = dims(model)
    h, H, std = c["h"], c["H"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def swiglu(ks, width):
        return {"gate_up": plain._linear(ks[4], (h, 2 * width), std),
                "down": plain._linear(ks[5], (width, h), std)}

    def layer(k, kind):
        ks = jax.random.split(k, 8)
        p = {"input_norm": {"scale": jnp.ones((h,), jnp.float32)},
             "post_attn_norm": {"scale": jnp.ones((h,), jnp.float32)},
             "attn": {"q": plain._linear(ks[0], (h, H * (c["dn"] + c["dr"])), std),
                      "kv_a": plain._linear(ks[1], (h, c["r"] + c["dr"]), std),
                      "kv_norm": {"scale": jnp.ones((c["r"],), jnp.float32)},
                      "kv_b": plain._linear(ks[2], (c["r"], H * (c["dn"] + c["dv"])), std),
                      "o": plain._linear(ks[3], (H * c["dv"], h), std)}}
        if kind == "dense":
            p["mlp"] = swiglu(ks, c["f"])
            return p
        kr, kgu, kd = jax.random.split(ks[7], 3)
        held = c["hi"] - c["lo"]
        p["mlp"] = {
            "router": {"w": jax.random.normal(kr, (h, c["E"])) * std,
                       "bias": jnp.zeros((c["E"],), jnp.float32)},
            "experts": {"gate_up": jax.random.normal(kgu, (held, h, 2 * c["fe"])) * std,
                        "down": jax.random.normal(kd, (held, c["fe"], h)) * std}}
        if c["fs"]:
            p["mlp"]["shared"] = swiglu(ks, c["fs"])
        return p

    keys = jax.random.split(klayers, c["L"])
    by_kind: dict = {}
    for i, kind in enumerate(kinds(c)):
        by_kind.setdefault(kind, []).append(layer(keys[i], kind))
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": {kind: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ls)
                   for kind, ls in by_kind.items()},
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": plain._linear(khead, (h, c["V"]), std),
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def rotate(x, seq: int, theta: float):
    """``x [s, ..., dr]``: neighbours ``(x[2i], x[2i + 1])`` turned by
    ``position x theta^(-2i/dr)``, left where they lie."""
    dr = x.shape[-1]
    inv = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape((seq,) + (1,) * (x.ndim - 2) + (dr // 2,))
    pairs = x.reshape(x.shape[:-1] + (dr // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return turned.reshape(x.shape)


def attention(q_nope, q_pe, k_nope, k_pe, v, mm):
    """One sequence, causal: q_nope ``[s, H, dn]``, q_pe ``[s, H, dr]``,
    k_nope ``[s, H, dn]``, k_pe ``[s, dr]`` (shared by the heads), v ``[s, H,
    dv]`` -> ``[s, H x dv]``.  Blocks of queries against the keys before them."""
    s, H, dn = q_nope.shape
    scale = 1.0 / math.sqrt(dn + q_pe.shape[-1])
    bq = plain._chunks(s, QUERY_BLOCK)

    def block(args):
        qn, qp, i = args
        qpos = i * bq + jnp.arange(bq)[:, None]
        visible = jnp.arange(s)[None, :] <= qpos
        sc = (mm("qhd,khd->hqk", qn, k_nope) + mm("qhd,kd->hqk", qp, k_pe)) * scale
        p = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", p, v)

    blocks = s // bq
    out = jax.lax.map(jax.checkpoint(block), (
        q_nope.reshape(blocks, bq, H, dn), q_pe.reshape(blocks, bq, H, -1),
        jnp.arange(blocks)))
    return out.reshape(s, -1)


def route(lp, z, c, left_out=()):
    """``(gates [t, E], chosen one-hot summed over the k choices [t, E])``."""
    s = jax.nn.sigmoid(jnp.matmul(z, lp["router"]["w"], precision=HIGHEST))
    bias = 0.0 if "bias" in left_out else lp["router"]["bias"]
    _, top_i = jax.lax.top_k(jax.lax.stop_gradient(s + bias), c["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if c["renorm"] and "renorm" not in left_out:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    if "scale" not in left_out:
        top_s = top_s * c["scale"]
    onehot = jax.nn.one_hot(top_i, c["E"], dtype=jnp.float32)  # [t, k, E]
    return jnp.einsum("tk,tke->te", top_s, onehot), jnp.sum(onehot, axis=1)


def expert_block(lp, z, c, mm, left_out=(), held=None):
    """The sparse MLP of ``z [t, h]`` -> ``(out, loads [E])``: the held experts
    (``held``: another range than the configuration's, for a test) over every
    token, weighted by the gate (zero for an expert a token did not choose),
    plus the shared expert."""
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
        if "shared" in lp and "shared" not in left_out:
            acc = acc + swiglu(zc, lp["shared"]["gate_up"]["w"], lp["shared"]["down"]["w"])
        return acc

    out = plain._over_chunks(chunk, (z, gates), TOKEN_CHUNK)
    return out, jax.lax.stop_gradient(jnp.sum(chosen, axis=0))


def layer_forward(lp, x, kind, c, mm, rows: int, left_out=(), held=None):
    """One layer of ``kind`` on ``x [rows * s, h]`` -> ``(x, loads [E])``."""
    H, dn, dr, dv, eps = c["H"], c["dn"], c["dr"], c["dv"], c["eps"]
    s = x.shape[0] // rows
    a = lp["attn"]
    y = plain._rms(x, lp["input_norm"]["scale"], eps)
    q = plain._over_chunks(lambda yc: mm("th,hf->tf", yc, a["q"]["w"]), y, TOKEN_CHUNK)
    ckv = plain._over_chunks(lambda yc: mm("th,hf->tf", yc, a["kv_a"]["w"]), y, TOKEN_CHUNK)
    latent, k_pe = ckv[:, :c["r"]], ckv[:, c["r"]:]
    if "latent_norm" not in left_out:
        latent = plain._rms(latent, a["kv_norm"]["scale"], eps)
    kv = plain._over_chunks(lambda lc: mm("tr,rf->tf", lc, a["kv_b"]["w"]), latent, TOKEN_CHUNK)
    outs = []
    for r in range(rows):
        sl = slice(r * s, (r + 1) * s)
        qh, kvh, kp = q[sl].reshape(s, H, dn + dr), kv[sl].reshape(s, H, dn + dv), k_pe[sl]
        q_pe = qh[..., dn:]
        if "rope" not in left_out:
            q_pe, kp = rotate(q_pe, s, c["theta"]), rotate(kp, s, c["theta"])
        outs.append(attention(qh[..., :dn], q_pe, kvh[..., :dn], kp, kvh[..., dn:], mm))
    att = jnp.concatenate(outs, axis=0)
    x = x + plain._over_chunks(lambda ac: mm("th,hf->tf", ac, a["o"]["w"]), att, TOKEN_CHUNK)
    z = plain._rms(x, lp["post_attn_norm"]["scale"], eps)
    if kind == "dense":
        return x + plain._dense_mlp(lp["mlp"], z, mm)[0], jnp.zeros((max(c["E"], 1),))
    out, loads = expert_block(lp["mlp"], z, c, mm, left_out, held)
    return x + out, loads


def microbatch_loss(params, tokens, c, quant=None, left_out=()):
    """``(loss, loads [sparse layers, E])`` of one micro-batch ``tokens [rows,
    seq]``: mean next-token cross entropy over its positions."""
    mm = plain._matmul(quant)
    rows, s = tokens.shape
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    at: dict = {}
    loads = []
    for kind in kinds(c):
        i = at.get(kind, 0)
        at[kind] = i + 1
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"][kind])
        x, load = jax.checkpoint(
            lambda lp, x, kind=kind: layer_forward(lp, x, kind, c, mm, rows, left_out))(lp, x)
        if kind == "sparse":
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
    loads = jnp.stack(loads) if loads else jnp.zeros((0, max(c["E"], 1)))
    return jnp.sum(per_tok) / jnp.sum(valid), loads


def batch_loss(params, tokens, c, quant=None, left_out=()):
    """``tokens [micro, rows, seq]`` -> ``(the mean of the micro-batches'
    losses, their loads summed)``."""
    def one(acc, mb):
        loss, loads = microbatch_loss(params, mb, c, quant, left_out)
        return (acc[0] + loss, acc[1] + loads), None
    n_sparse = c["L"] - c["dense"]
    zero = (jnp.zeros((), jnp.float32), jnp.zeros((n_sparse, max(c["E"], 1))))
    (total, loads), _ = jax.lax.scan(one, zero, tokens)
    return total / tokens.shape[0], loads


def move_bias(params, loads, gamma: float):
    """The selection bias after a step: up by ``gamma`` where an expert met
    fewer slots than the mean, down where more."""
    if "sparse" not in params["layers"]:
        return params
    sparse = params["layers"]["sparse"]
    router = sparse["mlp"]["router"]
    step = gamma * jnp.sign(jnp.mean(loads, axis=-1, keepdims=True) - loads)
    return {**params, "layers": {**params["layers"], "sparse": {
        **sparse, "mlp": {**sparse["mlp"], "router": {**router, "bias": router["bias"] + step}}}}}


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
