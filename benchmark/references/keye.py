"""The plain reference of a decoder whose attention keys a learned indexer
chooses, over softmax-routed experts (``architecture: keye``, ``model_type:
KeyeVL2``; the language model of huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B):
float32 ``jax.numpy`` forward, loss, gradients and AdamW, matmul precision
``highest``, no kernel, nothing of the program.

Equations (layer ``l``, input ``x`` [T, h]; linears without bias; every N an
RMSNorm of ``rms_norm_eps`` with a learned scale; H query and G key/value
heads of d dims; Hi index heads of di dims, ONE index key a token; ``t`` a
query, ``s <= t`` a key):

    u = N_in,l(x)                                             (input_norm)
    [q ; k ; v] = u W_qkv, heads of d;  q_i <- N_q(q_i), k_j <- N_k(k_j)
          (RMS over the d dims of every head; one learned scale for all query
          heads, one for all key heads); rope (rope_theta, all d dims, pairs
          (i, i + d/2)) on q and k
    the indexer, from u with its gradient stopped:
        qI = rope(u W_qI) [T, Hi, di];  kI = rope(LN(u W_kI)) [T, di]
              (LN a LayerNorm with scale and bias, eps = rms_norm_eps)
        w = u W_w [T, Hi]
        I[t, s] = (Hi di)^(-1/2) sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the min(topk, t + 1) keys s <= t of largest I[t, s]; ties go to the
          lower index; a constant (no gradient passes through the choice)
    A[t, i, .] = softmax_{s in S_t}(q_t,i . k_s,i//(H/G) / sqrt(d))
    x <- x + concat_i(sum_{s in S_t} A[t, i, s] v_s,i//(H/G)) W_o
    p_t = (1 / H) sum_i A[t, i, .], gradient stopped
    L_I,l = mean_t KL(p_t || softmax_{s in S_t} I[t, .])
    y = N_post,l(x)                                           (post_attn_norm)
    P = softmax(y W_r) over E;  T = the k largest;  w_e = P_e / sum_T P
    x <- x + sum_{e in T, lo <= e < hi} w_e W_down,e (silu(W_gate,e y) * (W_up,e y))
    aux_l = coef x E / k x sum_e f_e mean_t(P_e), f_e the mean slots expert e fills
    logits = N_final(x) W_head                                (untied head)
    loss = mean CE(next token) + mean_l aux_l + sum_l L_I,l

Only ``W_qI``, ``W_kI``, LN and ``W_w`` receive a gradient from ``L_I``, and
they receive none from anything else.  ``[lo, hi)`` is ``num_experts_held``:
the experts this chip holds; the sum leaves the other chips' experts out, the
router, its probabilities, the choice and the auxiliary loss run over all
``E``.

Assumed, where config.json is silent (the configuration file's ``assumed``
has each line): the indexer's form and its objective (DeepSeek-V3.2,
arXiv:2512.02556: the sparse stage's KL, coefficient 1 a layer); the head
norms (the Qwen3 lineage); softmax-then-top-k renormalised and a
load-balancing coefficient of 0.001; text positions only (the three streams
of ``mrope_section`` are equal, so the rope is the plain one); initializer
range 0.02, norms 1, LayerNorm bias 0; the leaf names.

RMSNorm, the rope, the matmuls (and the control's lower precisions), the
chunked position-wise parts and AdamW are the accepted reference's own
functions (``benchmark/reference.py``): the same plain ``jax.numpy``, not the
program.  This file's: the sizes, the seeded weights under the trainer's leaf
paths, the indexer, the selection (a stable descending sort of every row: the
rank of a key among the visible ones), the selected attention in blocks of
queries with the KL beside it, the softmax router with the held experts' sum.

Memory: a layer is recomputed in backward (only its input is kept), attention
runs in checkpointed blocks of ``QUERY_BLOCK`` queries against all the
sequence's keys (32 heads x 512 x 8192 scores a block), position-wise parts
and the head in checkpointed chunks of tokens.

``quant`` computes every matmul but the router's and the indexer's in a lower
precision: the *control* of the correctness check, never used by a benchmark
run.  ``left_out`` names parts a test leaves out to show that each is held:
``selection`` (dense causal attention in the sparse one's place),
``indexer_loss`` (no ``L_I``), ``float32_selection`` (index scores rounded to
bfloat16 before the choice), ``qk_norm``, ``rope``, ``index_rope``,
``index_norm``, ``head_weights``, ``renorm``, ``aux_loss``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as plain

leaf_names = plain.leaf_names
TOKEN_CHUNK = plain.TOKEN_CHUNK
QUERY_BLOCK = plain.QUERY_BLOCK
HIGHEST = plain.HIGHEST


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch not in ("keye", "keyevl2"):
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    experts = int(model["num_experts"])
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    sa = dict(model.get("sa_config") or {})
    return {
        "h": int(model["hidden_size"]),
        "L": int(model.get("num_hidden_layers", model.get("num_layers"))),
        "H": heads, "G": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "V": int(model["vocab_size"]),
        "theta": float(model.get("rope_theta", 1e7)),
        "eps": float(model.get("rms_norm_eps", 1e-6)),
        "std": float(model.get("initializer_range", 0.02)),
        "E": experts, "k": int(model.get("num_experts_per_tok", 8)),
        "lo": int(held[0]), "hi": int(held[1]),
        "fe": int(model.get("moe_intermediate_size", 768)),
        "renorm": bool(model.get("norm_topk_prob", True)),
        "aux_coef": float(model.get("router_aux_loss_coef", 0.001)),
        "Hi": int(sa.get("indexer_num_heads", 16)), "di": int(sa.get("indexer_head_dim", 64)),
        "topk": int(sa.get("topk", 2048)),
    }


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths: layer ``i`` from the ``i``-th of the
    layers' keys, the leading dim of every layer leaf the depth."""
    c = dims(model)
    h, H, G, d, std = c["h"], c["H"], c["G"], c["d"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def layer(k):
        ks = jax.random.split(k, 8)
        kq, kk, kw = jax.random.split(ks[5], 3)
        kr, kgu, kd = jax.random.split(ks[7], 3)
        held = c["hi"] - c["lo"]
        return {
            "input_norm": {"scale": jnp.ones((h,), jnp.float32)},
            "post_attn_norm": {"scale": jnp.ones((h,), jnp.float32)},
            "attn": {
                "qkv": plain._linear(ks[0], (h, (H + 2 * G) * d), std),
                "q_norm": {"scale": jnp.ones((d,), jnp.float32)},
                "k_norm": {"scale": jnp.ones((d,), jnp.float32)},
                "o": plain._linear(ks[3], (H * d, h), std),
                "indexer": {
                    "wq": plain._linear(kq, (h, c["Hi"] * c["di"]), std),
                    "wk": plain._linear(kk, (h, c["di"]), std),
                    "weights": plain._linear(kw, (h, c["Hi"]), std),
                    "k_norm": {"scale": jnp.ones((c["di"],), jnp.float32),
                               "bias": jnp.zeros((c["di"],), jnp.float32)}}},
            "mlp": {
                "router": {"w": jax.random.normal(kr, (h, c["E"])) * std},
                "experts": {"gate_up": jax.random.normal(kgu, (held, h, 2 * c["fe"])) * std,
                            "down": jax.random.normal(kd, (held, c["fe"], h)) * std}}}

    layers = [layer(k) for k in jax.random.split(klayers, c["L"])]
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": plain._linear(khead, (h, c["V"]), std),
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def indexer(ip, u, c, left_out=()):
    """One sequence's normed input ``u [s, h]`` (detached here) -> ``(qI [s,
    Hi, di], kI [s, di], w [s, Hi])``."""
    u = jax.lax.stop_gradient(u)
    s = u.shape[0]
    pos = jnp.arange(s)

    def proj(w):
        return plain._over_chunks(
            lambda uc: jnp.matmul(uc, w, precision=HIGHEST), u, TOKEN_CHUNK)

    q = proj(ip["wq"]["w"]).reshape(s, c["Hi"], c["di"])
    k = proj(ip["wk"]["w"])
    if "index_norm" not in left_out:
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(var + c["eps"]) * ip["k_norm"]["scale"] \
            + ip["k_norm"]["bias"]
    if "index_rope" not in left_out:
        q = plain._rope(q, pos, c["theta"])
        k = plain._rope(k[:, None, :], pos, c["theta"])[:, 0, :]
    w = proj(ip["weights"]["w"])
    if "head_weights" in left_out:
        w = jnp.ones_like(w)
    return q, k, w


def chosen(scores, visible, topk: int):
    """``scores [q, s]``, the keys a rule shows ``[q, s]`` -> the ``min(topk,
    visible)`` best-scored visible keys of every row (bool): a key's rank in a
    stable descending sort, so of equal scores the lower index goes first."""
    x = jnp.where(visible, jax.lax.stop_gradient(scores), -jnp.inf)
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return jnp.logical_and(visible, rank < topk)


def selected_attention(q, k, v, qi, ki, wi, c, mm, left_out=()):
    """One sequence: ``q [s, H, d]``, ``k`` / ``v`` ``[s, G, d]``, the
    indexer's ``qi``, ``ki``, ``wi`` -> ``(out [s, H d], sum_t KL_t, selected
    pairs)``.  Blocks of queries against all keys."""
    s, H, d = q.shape
    G = k.shape[1]
    bq = plain._chunks(s, QUERY_BLOCK)
    qg = q.reshape(s // bq, bq, G, H // G, d)
    scale = 1.0 / math.sqrt(c["Hi"] * c["di"])

    def block(args):
        qb, qib, wib, i = args
        qpos = i * bq + jnp.arange(bq)[:, None]
        visible = jnp.arange(s)[None, :] <= qpos
        dots = jnp.einsum("qjd,sd->jqs", qib, ki, precision=HIGHEST)
        index = jnp.einsum("jqs,qj->qs", jax.nn.relu(dots), wib, precision=HIGHEST) * scale
        if "selection" in left_out:
            sel = visible
        elif "float32_selection" in left_out:
            sel = chosen(index.astype(jnp.bfloat16).astype(jnp.float32), visible, c["topk"])
        else:
            sel = chosen(index, visible, c["topk"])
        sc = mm("qngd,knd->ngqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(sel[None, None], sc, -jnp.inf), axis=-1)
        out = mm("ngqk,knd->qngd", p, v)
        target = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))           # [q, s]
        log_q = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1)
        kl = jnp.where(target > 0,
                       target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                 - jnp.where(sel, log_q, 0.0)), 0.0)
        return out, jnp.sum(kl), jnp.sum(sel.astype(jnp.float32))

    out, kl, kept = jax.lax.map(jax.checkpoint(block), (
        qg, qi.reshape(s // bq, bq, c["Hi"], c["di"]), wi.reshape(s // bq, bq, c["Hi"]),
        jnp.arange(s // bq)))
    return out.reshape(s, H * d), jnp.sum(kl), jnp.sum(kept)


def attention_half(ap, u, c, mm, rows: int, left_out=()):
    """The first half of a layer on the normed ``u [rows * s, h]`` -> ``(its
    output before the residual, L_I, selected pairs)``."""
    H, G, d = c["H"], c["G"], c["d"]
    s = u.shape[0] // rows
    qkv = plain._over_chunks(lambda uc: mm("th,hf->tf", uc, ap["qkv"]["w"]), u, TOKEN_CHUNK)
    qh, kh, vh = jnp.split(qkv, [H * d, (H + G) * d], axis=-1)
    pos = jnp.arange(s)
    outs, kl, kept = [], 0.0, 0.0
    for r in range(rows):
        sl = slice(r * s, (r + 1) * s)
        q, k = qh[sl].reshape(s, H, d), kh[sl].reshape(s, G, d)
        if "qk_norm" not in left_out:
            q = plain._rms(q, ap["q_norm"]["scale"], c["eps"])
            k = plain._rms(k, ap["k_norm"]["scale"], c["eps"])
        if "rope" not in left_out:
            q, k = plain._rope(q, pos, c["theta"]), plain._rope(k, pos, c["theta"])
        out, kl_r, kept_r = selected_attention(
            q, k, vh[sl].reshape(s, G, d), *indexer(ap["indexer"], u[sl], c, left_out),
            c, mm, left_out)
        outs.append(out)
        kl, kept = kl + kl_r, kept + kept_r
    att = jnp.concatenate(outs, axis=0)
    out = plain._over_chunks(lambda ac: mm("th,hf->tf", ac, ap["o"]["w"]), att, TOKEN_CHUNK)
    return out, kl / (rows * s), kept


def expert_block(lp, z, c, mm, left_out=(), held=None):
    """The sparse MLP of ``z [t, h]`` -> ``(out, (auxiliary loss, loads
    [E]))``: the held experts (``held``: another range than the
    configuration's, for a test) over every token, weighted by the gate (zero
    for an expert a token did not choose)."""
    lo, hi = held or (c["lo"], c["hi"])
    probs = jax.nn.softmax(jnp.matmul(z, lp["router"]["w"], precision=HIGHEST), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, c["k"])
    if c["renorm"] and "renorm" not in left_out:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, c["E"], dtype=jnp.float32)  # [t, k, E]
    gates = jnp.einsum("tk,tke->te", top_p, onehot)[:, lo:hi]
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = c["aux_coef"] * c["E"] * jnp.sum(f_e * jnp.mean(probs, axis=0)) / c["k"]
    if "aux_loss" in left_out:
        aux = 0.0 * aux

    def chunk(args):
        zc, gc = args

        def one(acc, ew):
            gate, up = jnp.split(mm("th,hf->tf", zc, ew["gate_up"]), 2, axis=-1)
            out = mm("tf,fh->th", jax.nn.silu(gate) * up, ew["down"])
            return acc + out * ew["g"][:, None], None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(zc),
                              {**lp["experts"], "g": gc.T})
        return acc

    out = plain._over_chunks(chunk, (z, gates), TOKEN_CHUNK)
    loads = jax.lax.stop_gradient(jnp.sum(onehot, axis=(0, 1)))
    return out, (aux, loads)


def layer_forward(lp, x, c, mm, rows: int, left_out=()):
    """One layer on ``x [rows * s, h]`` -> ``(x, [aux, L_I, selected pairs])``."""
    u = plain._rms(x, lp["input_norm"]["scale"], c["eps"])
    out, kl, kept = attention_half(lp["attn"], u, c, mm, rows, left_out)
    x = x + out
    y = plain._rms(x, lp["post_attn_norm"]["scale"], c["eps"])
    out, (aux, _) = expert_block(lp["mlp"], y, c, mm, left_out)
    if "indexer_loss" in left_out:
        kl = 0.0 * kl
    return x + out, jnp.stack([aux, kl, jax.lax.stop_gradient(kept)])


def microbatch_loss(params, tokens, c, quant=None, left_out=()):
    """``(loss, {"lm_loss", "router_aux_loss", "indexer_loss", "kept_pairs"})``
    of one micro-batch ``tokens [rows, seq]``: mean next-token cross entropy
    over its positions + the mean of the layers' auxiliary losses + the sum of
    their ``L_I``."""
    mm = plain._matmul(quant)
    rows, s = tokens.shape
    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    sums = jnp.zeros((3,), jnp.float32)
    for i in range(c["L"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x, parts = jax.checkpoint(
            lambda lp, x: layer_forward(lp, x, c, mm, rows, left_out))(lp, x)
        sums = sums + parts
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
    lm = jnp.sum(per_tok) / jnp.sum(valid)
    aux, kl = sums[0] / c["L"], sums[1]
    return lm + aux + kl, {"lm_loss": lm, "router_aux_loss": aux, "indexer_loss": kl,
                           "kept_pairs": sums[2] / c["L"]}


def batch_loss(params, tokens, c, quant=None, left_out=()):
    """``tokens [micro, rows, seq]`` -> the mean of the micro-batches' losses."""
    losses, _ = jax.lax.map(
        lambda mb: microbatch_loss(params, mb, c, quant, left_out), tokens)
    return jnp.mean(losses)


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
            loss, grads = jax.value_and_grad(batch_loss)(params, tokens, c, quant, left_out)
            grads = place(grads)
            params, mu, nu, gnorm, leaf_norms = plain.adamw(
                params, grads, mu, nu, step1, lr, optim, clip)
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
