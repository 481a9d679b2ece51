"""The plain reference: float32 ``jax.numpy`` forward, loss, gradients and
AdamW for the two families the cells train (dense Mistral-style decoder,
Mixtral-style sparse experts), with matmul precision ``highest``.

It imports nothing of the program and takes nothing the program made.  It
makes its own weights from the seed by the same published recipe the trainer
documents (truncated normal, std ``initializer_range``, for the dense leaves;
plain normal for router and experts; norm scales 1) drawn with the same key
derivation, so that both sides start from the same numbers; the tree it
returns uses the trainer's leaf paths so that leaves can be compared by name.
That is the one place where it knows the program's layout: a program change
that renames leaves or re-derives keys shows as ``correct: false`` with a
parameter change of the size of the weights themselves.

Equations (per layer, pre-norm):

    h  = x + Wo . attn(rope(q), rope(k), v),  [q k v] = Wqkv . rms(x)
    x' = h + mlp(rms(h))
    mlp(y)  = Wdown . (silu(Wgate y) * (Wup y))                      (dense)
    mlp(y)  = sum_{e in top2} p_e / (p_a + p_b) . mlp_e(y),
              p = softmax(Wr y)                                      (experts)
    loss    = mean CE(next token) [+ coef . E/k . sum_e f_e P_e per layer]

Attention is causal with a sliding window: query ``i`` sees keys ``j`` with
``i - window < j <= i``.  ``f_e`` is the mean number of top-k slots expert
``e`` fills per token, ``P_e`` its mean router probability.

Memory: position-wise parts run in checkpointed chunks of tokens, attention
in checkpointed blocks of queries against the window of keys, so one 32k
sequence fits beside float32 weights, gradients and AdamW state.

``quant`` computes every matmul but the router's in a lower precision
(``_matmul``): the *control* of the correctness check (PERF.md), never used by
a benchmark run.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TOKEN_CHUNK = 2048
QUERY_BLOCK = 512


# --------------------------------------------------------------------------
# sizes
# --------------------------------------------------------------------------


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch not in ("mistral", "llama", "mixtral"):
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    nh, h = int(model["num_attention_heads"]), int(model["hidden_size"])
    moe = dict(model.get("moe") or {})
    if arch == "mixtral" and not moe.get("dropless", True):
        raise ValueError("reference: only dropless routing is written down")
    return {
        "moe": arch == "mixtral",
        "h": h, "f": int(model["intermediate_size"]),
        "L": int(model["num_layers"]), "nh": nh,
        "nkv": int(model.get("num_key_value_heads") or nh),
        "d": int(model.get("head_dim") or h // nh),
        "V": int(model["vocab_size"]),
        "window": model.get("sliding_window"),
        "theta": float(model.get("rope_theta", 10000.0)),
        "eps": float(model.get("rms_norm_eps", 1e-5)),
        "std": float(model.get("initializer_range", 0.02)),
        "E": int(moe.get("num_experts", 0) or 0),
        "k": int(moe.get("top_k", 0) or 0),
        "aux_coef": float(moe.get("router_aux_loss_coef", 0.0) or 0.0),
    }


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def _trunc(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def _linear(key, shape, std):
    wkey, _ = jax.random.split(key)
    return {"w": _trunc(wkey, shape, std)}


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)`` (an argument, so that
    one compiled program serves every seed); the leading dim of every layer
    leaf is the depth."""
    c = dims(model)
    h, f, d, nh, nkv, std = c["h"], c["f"], c["d"], c["nh"], c["nkv"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def layer(k):
        ks = jax.random.split(k, 6)
        return {
            "input_norm": {"scale": jnp.ones((h,), jnp.float32)},
            "post_attn_norm": {"scale": jnp.ones((h,), jnp.float32)},
            "attn": {"qkv": _linear(ks[0], (h, (nh + 2 * nkv) * d), std),
                     "o": _linear(ks[3], (nh * d, h), std)},
            "mlp": {"gate_up": _linear(ks[4], (h, 2 * f), std),
                    "down": _linear(ks[5], (f, h), std)},
        }

    def experts(k):
        kr, kgu, kd = jax.random.split(k, 3)
        e = c["E"]
        return {
            "router": {"w": jax.random.normal(kr, (h, e)) * std},
            "experts": {"gate_up": jax.random.normal(kgu, (e, h, 2 * f)) * std,
                        "down": jax.random.normal(kd, (e, f, h)) * std},
        }

    def stack(fn, keys):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *[fn(k) for k in keys])

    layers = stack(layer, jax.random.split(klayers, c["L"]))
    if c["moe"]:
        layers["mlp"] = stack(
            experts, jax.random.split(jax.random.fold_in(key, 999), c["L"]))
    return {
        "embed": {"embedding": _trunc(kemb, (c["V"], h), std)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": _linear(khead, (h, c["V"]), std),
    }


def leaf_names(tree) -> list[str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(p, "key", p)) for p in path) for path, _ in flat]


# --------------------------------------------------------------------------
# lower precisions (the control)
# --------------------------------------------------------------------------


def _scaled_cast(x, dtype, top):
    """Round to an 8-bit float with one scale per tensor (largest value at
    the format's top), back in float32."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _matmul(quant: Optional[str]):
    """``mm(spec, a, b)``: an einsum in float32 at ``highest``, or the same
    in a lower precision:

    - ``bf16``: both operands rounded to bfloat16 (value only; gradients pass
      unrounded), float32 accumulation;
    - ``fp8``: the usual fp8 training recipe.  Forward: both operands in
      e4m3.  Backward: the incoming gradient in e5m2, times the e4m3
      operands the forward pass kept.  One scale per tensor, float32
      accumulation.
    """
    def plain(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    if quant is None:
        return plain
    if quant == "bf16":
        def rnd(x):
            return x + jax.lax.stop_gradient(
                x.astype(jnp.bfloat16).astype(jnp.float32) - x)
        return lambda spec, a, b: plain(spec, rnd(a), rnd(b))
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")

    def e4m3(x):
        return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)

    @partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm8(spec, a, b):
        return plain(spec, e4m3(a), e4m3(b))

    def fwd(spec, a, b):
        qa, qb = e4m3(a), e4m3(b)
        return plain(spec, qa, qb), (qa, qb)

    def bwd(spec, kept, g):
        _, vjp = jax.vjp(lambda x, y: plain(spec, x, y), *kept)
        return vjp(_scaled_cast(g, jnp.float8_e5m2, 57344.0))

    mm8.defvjp(fwd, bwd)
    return mm8


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Half-rotation layout: pairs are ``(x[i], x[i + d/2])``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(0, 2 * half, 2, dtype=np.float64) / (2 * half)))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _chunks(n: int, size: int) -> int:
    size = min(size, n)
    while n % size:
        size -= 1
    return size


def _over_chunks(fn, xs, size):
    """``fn`` over equal chunks of the leading dim, each chunk checkpointed."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    c = _chunks(n, size)
    split = jax.tree_util.tree_map(
        lambda a: a.reshape((n // c, c) + a.shape[1:]), xs)
    out = jax.lax.map(jax.checkpoint(fn), split)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n,) + a.shape[2:]), out)


def _attention(q, k, v, window, mm):
    """One sequence: q ``[s, nh, d]``, k and v ``[s, nkv, d]``; causal, with
    a sliding window.  Blocks of queries against the keys they can see."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    bq = _chunks(s, QUERY_BLOCK)
    w = s if not window else min(int(window), s)
    span = min(s, bq + w)  # keys a block of queries can see, as a fixed length
    qg = q.reshape(s // bq, bq, nkv, nh // nkv, d)

    def block(args):
        qb, i = args
        q0 = i * bq
        k0 = jnp.clip(q0 + bq - span, 0, s - span)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
        qpos = q0 + jnp.arange(bq)[:, None]
        kpos = k0 + jnp.arange(span)[None, :]
        visible = (kpos <= qpos) & (kpos > qpos - w)
        sc = mm("qngd,knd->ngqk", qb, kb) / math.sqrt(d)
        sc = jnp.where(visible[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("ngqk,knd->qngd", p, vb)

    out = jax.lax.map(jax.checkpoint(block), (qg, jnp.arange(s // bq)))
    return out.reshape(s, nh * d)


def _dense_mlp(lp, y, mm):
    def chunk(yc):
        gu = mm("th,hf->tf", yc, lp["gate_up"]["w"])
        gate, up = jnp.split(gu, 2, axis=-1)
        return mm("tf,fh->th", jax.nn.silu(gate) * up, lp["down"]["w"])
    return _over_chunks(chunk, y, TOKEN_CHUNK), 0.0


def _expert_mlp(lp, y, c, mm):
    """Every expert over every token, weighted by the gate (zero for the
    experts a token is not routed to): the same sum as a dropless dispatch."""
    logits = jnp.matmul(y, lp["router"]["w"], precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, c["k"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, c["E"], dtype=jnp.float32)  # [t, k, E]
    gates = jnp.einsum("tk,tke->te", top_p, onehot)
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = c["aux_coef"] * c["E"] * jnp.sum(f_e * jnp.mean(probs, axis=0)) / c["k"]

    def chunk(args):
        yc, gc = args

        def one(acc, ew):
            gu = mm("th,hf->tf", yc, ew["gate_up"])
            gate, up = jnp.split(gu, 2, axis=-1)
            out = mm("tf,fh->th", jax.nn.silu(gate) * up, ew["down"])
            return acc + out * ew["g"][:, None], None

        ews = {"gate_up": lp["experts"]["gate_up"], "down": lp["experts"]["down"],
               "g": gc.T}
        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(yc), ews)
        return acc

    return _over_chunks(chunk, (y, gates), TOKEN_CHUNK), aux


def microbatch_loss(params, tokens, c, quant=None):
    """Loss of one micro-batch ``tokens [rows, seq]``: mean next-token cross
    entropy over all its positions, plus the router's load-balancing term
    taken over all its tokens."""
    mm = _matmul(quant)
    rows, s = tokens.shape
    nh, nkv, d = c["nh"], c["nkv"], c["d"]
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    pos = jnp.arange(s)
    aux_total = 0.0
    for li in range(c["L"]):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        y = _rms(x, lp["input_norm"]["scale"], c["eps"])
        qkv = _over_chunks(
            lambda yc: mm("th,hf->tf", yc, lp["attn"]["qkv"]["w"]), y, TOKEN_CHUNK)
        qh, kh, vh = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
        outs = []
        for r in range(rows):
            sl = slice(r * s, (r + 1) * s)
            outs.append(_attention(
                _rope(qh[sl].reshape(s, nh, d), pos, c["theta"]),
                _rope(kh[sl].reshape(s, nkv, d), pos, c["theta"]),
                vh[sl].reshape(s, nkv, d), c["window"], mm))
        att = jnp.concatenate(outs, axis=0)
        x = x + _over_chunks(
            lambda ac: mm("th,hf->tf", ac, lp["attn"]["o"]["w"]), att, TOKEN_CHUNK)
        y = _rms(x, lp["post_attn_norm"]["scale"], c["eps"])
        if c["moe"]:
            out, aux = _expert_mlp(lp["mlp"], y, c, mm)
        else:
            out, aux = _dense_mlp(lp["mlp"], y, mm)
        x = x + out
        aux_total = aux_total + aux
    y = _rms(x, params["final_norm"]["scale"], c["eps"])
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

    per_tok = _over_chunks(ce, (y, targets, valid), TOKEN_CHUNK)
    loss = jnp.sum(per_tok) / jnp.sum(valid)
    return loss + aux_total / c["L"]


def batch_loss(params, tokens, c, quant=None):
    """``tokens [micro, rows, seq]``: the mean of the micro-batches' losses
    (gradient accumulation averages them)."""
    def one(acc, mb):
        return acc + microbatch_loss(params, mb, c, quant), None
    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), tokens)
    return total / tokens.shape[0]


# --------------------------------------------------------------------------
# AdamW with global-norm clipping and the linear warm-up / linear decay rate
# --------------------------------------------------------------------------


def learning_rate(optim: Mapping[str, Any], step0: int) -> float:
    """Rate of the update that follows ``step0`` finished updates."""
    sched = dict(optim.get("sched") or {})
    lr, warm = float(optim["lr"]), int(sched.get("warmup_steps", 0) or 0)
    total, floor = int(sched["max_steps"]), float(sched.get("min_lr", 0.0) or 0.0)
    if step0 < warm:
        return lr * step0 / max(1, warm)
    frac = min(max((step0 - warm) / max(1, total - warm), 0.0), 1.0)
    return lr + frac * (floor - lr)


def _decays(name: str) -> bool:
    return not any(s in name for s in ("norm", "bias", "scale"))


def adamw(params, grads, mu, nu, step1, lr, optim, clip):
    """One update; ``step1`` counts from 1.  Returns the clipped gradient's
    per-leaf norms too: what the optimizer was given."""
    b1, b2 = (float(b) for b in optim.get("betas", (0.9, 0.999)))
    eps, wd = float(optim.get("eps", 1e-8)), float(optim.get("weight_decay", 0.01))
    sq = jax.tree_util.tree_map(lambda g: jnp.sum(g * g), grads)
    gnorm = jnp.sqrt(sum(jax.tree_util.tree_leaves(sq)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6)) if clip else 1.0
    names = leaf_names(params)
    decay = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), [float(_decays(n)) for n in names])
    c1, c2 = 1.0 - b1 ** step1, 1.0 - b2 ** step1

    def leaf(p, g, m, v, dk):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * dk * p
        return p - lr * upd, m, v

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu, decay)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    leaf_norms = jax.tree_util.tree_map(lambda s: jnp.sqrt(s) * scale, sq)
    return pick(0), pick(1), pick(2), gnorm, leaf_norms


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
            params, mu, nu, gnorm, leaf_norms = adamw(
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
    parameters' change after the last step (``dparam``).

    ``shard`` places a tree on devices (several chips: PERF.md); default is
    the first device."""
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
            lr = learning_rate(optim, i)
            params, mu, nu, loss, gnorm, leaf_norms = step(
                params, mu, nu, jnp.asarray(tokens, jnp.int32),
                jnp.float32(i + 1), jnp.float32(lr))
            out["loss"].append(float(loss))
            out["grad_norm"].append(float(gnorm))
            if i == 0:
                out["grad1"] = dict(zip(names, (
                    float(x) for x in jax.tree_util.tree_leaves(leaf_norms))))
        out["dparam"] = dict(zip(names, (
            float(x) for x in jax.tree_util.tree_leaves(change(params, key)))))
        del params, mu, nu
    return out
