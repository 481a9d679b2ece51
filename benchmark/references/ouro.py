"""The plain reference of the looped decoder (``architecture: ouro``,
arXiv:2510.25741): float32 ``jax.numpy`` forward, loss, gradients and AdamW,
matmul precision ``highest``, no kernel, nothing of the program.

Equations (T = ``total_ut_steps`` passes over the SAME L layers' weights, S
tokens, y the next-token labels; every N an RMSNorm with a learned scale):

    h_0 = E[x]
    for t = 1..T:
        h = h_{t-1}
        for l = 1..L:
            h = h + N2_l( Wo . attn(rope(q), rope(k), v) ),  [q k v] = Wqkv . N1_l(h)
            h = h + N4_l( Wdown . (silu(Wgate y) * (Wup y)) ),  y = N3_l(h)
        h_t = N_f(h)                      the NORMED state feeds head, gate and pass t+1
        ce_t[i] = -log softmax(h_t[i] W_head)[y[i]]
        g_t[i]  = sigmoid(h_t[i] . w_g + b_g)
    p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j);  p_T = prod_{j<T} (1 - g_j)
    loss = mean_i [ sum_t p_t[i] ce_t[i] - beta H(p[i]) ],  H(p) = -sum_t p_t log p_t

Attention (causal, optional sliding window), RoPE, the chunked position-wise
parts, the lower-precision matmuls of the control and AdamW are the accepted
reference's own functions (``benchmark/reference.py``): the same plain
``jax.numpy``, not the program.  What is this file's: the sizes, the seeded
weights under the trainer's leaf paths (four norms a layer, the exit gate), the
loop over passes, the per-pass head and the exit mixture.

Memory: a layer application is recomputed in backward (only its input is
kept: 32 x 32 MiB at 8 layers x 4 passes x 4096 tokens), the head runs in
checkpointed chunks of tokens, so the run fits beside float32 weights,
gradients and AdamW state.

``quant`` computes every matmul (the gate is a float32 dot product, not one)
in a lower precision: the *control* of the correctness check, never used by a
benchmark run.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as plain

leaf_names = plain.leaf_names
TOKEN_CHUNK = plain.TOKEN_CHUNK


def dims(model: Mapping[str, Any]) -> dict:
    arch = str(model.get("architecture", "")).lower()
    if arch != "ouro":
        raise ValueError(f"reference has no equations for architecture {arch!r}")
    nh, h = int(model["num_attention_heads"]), int(model["hidden_size"])
    return {
        "h": h, "f": int(model["intermediate_size"]),
        "L": int(model["num_layers"]), "T": int(model.get("total_ut_steps", 4)),
        "nh": nh, "nkv": int(model.get("num_key_value_heads") or nh),
        "d": int(model.get("head_dim") or h // nh),
        "V": int(model["vocab_size"]),
        "window": model.get("sliding_window"),
        "theta": float(model.get("rope_theta", 10000.0)),
        "eps": float(model.get("rms_norm_eps", 1e-5)),
        "std": float(model.get("initializer_range", 0.02)),
        "beta": float(model.get("exit_entropy_beta", 0.1)),
    }


NORMS = ("input_norm", "input_norm_2", "post_attn_norm", "post_attn_norm_2")


def init_params(model: Mapping[str, Any], key) -> dict:
    """Weights from ``key = jax.random.PRNGKey(seed)``, drawn as the trainer
    draws them, under its leaf paths; the leading dim of every layer leaf is
    the depth."""
    c = dims(model)
    h, f, d, nh, nkv, std = c["h"], c["f"], c["d"], c["nh"], c["nkv"], c["std"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def layer(k):
        ks = jax.random.split(k, 6)
        return {
            **{n: {"scale": jnp.ones((h,), jnp.float32)} for n in NORMS},
            "attn": {"qkv": plain._linear(ks[0], (h, (nh + 2 * nkv) * d), std),
                     "o": plain._linear(ks[3], (nh * d, h), std)},
            "mlp": {"gate_up": plain._linear(ks[4], (h, 2 * f), std),
                    "down": plain._linear(ks[5], (f, h), std)},
        }

    layers = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[layer(k) for k in jax.random.split(klayers, c["L"])])
    gate = plain._linear(jax.random.fold_in(key, 777), (h, 1), std)
    return {
        "embed": {"embedding": plain._trunc(kemb, (c["V"], h), std)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), jnp.float32)},
        "lm_head": plain._linear(khead, (h, c["V"]), std),
        "exit_gate": {"w": gate["w"], "bias": jnp.zeros((1,), jnp.float32)},
    }


def exit_distribution(z):
    """``z [T, n]`` gate logits -> ``(p [T, n], H [n])``; the last pass takes
    what is left and its own gate is unused."""
    g = jax.nn.sigmoid(z[:-1])
    stay = jnp.cumprod(1.0 - g, axis=0)
    one = jnp.ones_like(z[:1])
    p = jnp.concatenate([g, one], axis=0) * jnp.concatenate([one, stay], axis=0)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    return p, -jnp.sum(plogp, axis=0)


def microbatch_loss(params, tokens, c, quant=None):
    """Loss of one micro-batch ``tokens [rows, seq]`` and, beside it, the
    per-pass token means of ``ce_t`` and ``p_t`` and the mean entropy."""
    mm = plain._matmul(quant)
    rows, s = tokens.shape
    nh, nkv, d, eps = c["nh"], c["nkv"], c["d"], c["eps"]
    pos = jnp.arange(s)
    # next-token targets: the last position of each row predicts nothing
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((rows, 1), tokens.dtype)], axis=1).reshape(-1)
    valid = jnp.concatenate(
        [jnp.ones((rows, s - 1), jnp.float32), jnp.zeros((rows, 1), jnp.float32)],
        axis=1).reshape(-1)

    @jax.checkpoint
    def layer(x, lp):
        y = plain._rms(x, lp["input_norm"]["scale"], eps)
        qkv = plain._over_chunks(
            lambda yc: mm("th,hf->tf", yc, lp["attn"]["qkv"]["w"]), y, TOKEN_CHUNK)
        qh, kh, vh = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
        outs = []
        for r in range(rows):
            sl = slice(r * s, (r + 1) * s)
            outs.append(plain._attention(
                plain._rope(qh[sl].reshape(s, nh, d), pos, c["theta"]),
                plain._rope(kh[sl].reshape(s, nkv, d), pos, c["theta"]),
                vh[sl].reshape(s, nkv, d), c["window"], mm))
        att = plain._over_chunks(
            lambda ac: mm("th,hf->tf", ac, lp["attn"]["o"]["w"]),
            jnp.concatenate(outs, axis=0), TOKEN_CHUNK)
        x = x + plain._rms(att, lp["input_norm_2"]["scale"], eps)
        y = plain._rms(x, lp["post_attn_norm"]["scale"], eps)
        out, _ = plain._dense_mlp(lp["mlp"], y, mm)
        return x + plain._rms(out, lp["post_attn_norm_2"]["scale"], eps), None

    def head(args):
        yc, tc, vc = args
        logits = mm("th,hv->tv", yc, params["lm_head"]["w"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return (lse - picked) * vc

    def one_pass(x, _):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = plain._rms(x, params["final_norm"]["scale"], eps)
        ce = plain._over_chunks(head, (x, targets, valid), TOKEN_CHUNK)
        z = jnp.sum(x * params["exit_gate"]["w"][:, 0], axis=-1) + params["exit_gate"]["bias"][0]
        return x, (ce, z)

    x = params["embed"]["embedding"][tokens.reshape(-1)]  # [rows*s, h]
    _, (ce, z) = jax.lax.scan(one_pass, x, None, length=c["T"])
    p, entropy = exit_distribution(z)
    n = jnp.sum(valid)
    loss = jnp.sum((jnp.sum(p * ce, axis=0) - c["beta"] * entropy) * valid) / n
    return loss, {"ce": jnp.sum(ce, axis=1) / n, "p": jnp.sum(p * valid, axis=1) / n,
                  "entropy": jnp.sum(entropy * valid) / n}


def batch_loss(params, tokens, c, quant=None):
    """``tokens [micro, rows, seq]``: the mean of the micro-batches' losses
    (gradient accumulation averages them)."""
    def one(acc, mb):
        return acc + microbatch_loss(params, mb, c, quant)[0], None
    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), tokens)
    return total / tokens.shape[0]


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
        out["dparam"] = dict(zip(names, (
            float(x) for x in jax.tree_util.tree_leaves(change(params, key)))))
        del params, mu, nu
    return out
