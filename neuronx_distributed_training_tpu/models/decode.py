"""KV-cache autoregressive decoding (Llama / Mixtral / Megatron-GPT).

The reference's SFT-evaluation inference path is a traced decoder with KV
caching (``sft_evaluation/models/nxd_llama.py`` LlamaRunner); the plain
``models.generate`` here re-runs the full prefix per token — fine for tiny
evals, O(n^2 · L) wrong for real generation.  This module is the cached
path:

- ``prefill``: one causal forward over the right-padded prompts that also
  captures each layer's rotated K and V into the cache;
- ``decode_step``: a single-token forward attending over ``cache[: pos+1]``
  per row (static ``max_len`` buffer + position mask — XLA-friendly, no
  dynamic shapes);
- ``generate_cached``: drop-in for ``generate`` (same right-padded /
  front-writing convention, so generated tokens land exactly on the cache
  slots the row's prompt padding occupied, and the position mask keeps stale
  pad entries invisible).

Parity with the uncached path is test-enforced (greedy outputs must match
``models.generate`` exactly).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


def _qkv(lp, x, cfg: llama.LlamaConfig):
    b, s, _ = x.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_size
    if cfg.fuse_qkv:
        qkv = linear_ops.apply_linear(lp["qkv"], x)
        q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    else:
        q = linear_ops.apply_linear(lp["q"], x)
        k = linear_ops.apply_linear(lp["k"], x)
        v = linear_ops.apply_linear(lp["v"], x)
    return (q.reshape(b, s, nh, d), k.reshape(b, s, nkv, d),
            v.reshape(b, s, nkv, d))


def prefill(params, input_ids: jax.Array, cfg: llama.LlamaConfig,
            policy: DtypePolicy, *, max_len: Optional[int] = None):
    """Causal forward capturing the KV cache.

    Returns ``(hidden [b, s, h], cache {"k","v"}: [L, b, max_len, kvh, d])``
    with rotated keys; cache tail beyond ``s`` is zeros (masked out by
    position during decode).  Callers take logits where they need them
    (``llama.logits_fn``) — generation only reads ONE position per row, and a
    full [b, s, vocab] logits tensor is the dominant prefill allocation.

    The layer math is ``llama._decoder_layer(return_kv=True)`` — shared code,
    shared sharding constraints, so TP/SP prefill shards like training.
    """
    s = input_ids.shape[1]
    max_len = max_len or s
    aspec = shd.act_spec(cfg.sequence_parallel, cfg.context_parallel)
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype
    )
    x = shd.constrain(x, aspec)
    cos, sin = llama._rope_for(input_ids, cfg)
    layer_stack = policy.cast_to_compute(params["layers"])

    def body(x, lp):
        x, (k, v) = llama._decoder_layer(lp, x, cos, sin, cfg, policy,
                                         return_kv=True)
        # pad the cached block out to max_len (static)
        pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]
        return x, (jnp.pad(k, pad), jnp.pad(v, pad))

    x, (ck, cv) = jax.lax.scan(body, x, layer_stack)
    h = norm_ops.apply_rms_norm(params["final_norm"], x, eps=cfg.rms_norm_eps)
    return h, {"k": ck, "v": cv}


def _cached_attn(q, k_new, v_new, ck, cv, pos, *, sliding_window,
                 softmax_dtype):
    """Write this step's KV at ``pos`` per row, attend q over ``<= pos``.

    q/k_new/v_new [b, 1, heads, d]; ck/cv [b, max_len, kvh, d].
    Returns (out [b, 1, nh*d], ck, cv).
    """
    b, _, nh, d = q.shape
    nkv = ck.shape[2]
    max_len = ck.shape[1]
    rows = jnp.arange(b)
    ck = ck.at[rows, pos].set(k_new[:, 0].astype(ck.dtype))
    cv = cv.at[rows, pos].set(v_new[:, 0].astype(cv.dtype))
    kk = jnp.repeat(ck, nh // nkv, axis=2) if nkv != nh else ck
    vv = jnp.repeat(cv, nh // nkv, axis=2) if nkv != nh else cv
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, kk, preferred_element_type=softmax_dtype
    ) * (1.0 / (d ** 0.5))
    valid = jnp.arange(max_len)[None, :] <= pos[:, None]
    if sliding_window is not None:
        valid = valid & (jnp.arange(max_len)[None, :]
                         > pos[:, None] - sliding_window)
    neg = jnp.asarray(jnp.finfo(softmax_dtype).min / 2, softmax_dtype)
    scores = jnp.where(valid[:, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores.astype(softmax_dtype), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vv.dtype), vv)
    return out.reshape(b, 1, nh * d).astype(q.dtype), ck, cv


def decode_step(params, cache: dict, tokens: jax.Array, pos: jax.Array,
                cfg: llama.LlamaConfig, policy: DtypePolicy):
    """One token per row: write KV at ``pos[b]``, attend over ``<= pos[b]``.

    ``tokens [b]`` int32, ``pos [b]`` the buffer position being filled.
    Returns ``(logits [b, vocab], new_cache)``.
    """
    x = linear_ops.apply_embedding(
        params["embed"], tokens[:, None], compute_dtype=policy.compute_dtype
    )
    inv_freq = rope_ops.rope_frequencies(
        cfg.head_size, theta=cfg.rope_theta,
        position_interpolation_factor=cfg.rope_interpolation_factor,
    )
    cos, sin = rope_ops.rope_cos_sin(pos[:, None], inv_freq, dtype=jnp.float32)
    layer_stack = policy.cast_to_compute(params["layers"])

    def body(x, inp):
        lp, ck, cv = inp  # ck/cv [b, max_len, nkv, d]
        residual = x
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=cfg.rms_norm_eps)
        q, k, v = _qkv(lp["attn"], hidden, cfg)  # [b, 1, ., d]
        q = rope_ops.apply_rope(q, cos, sin)
        k = rope_ops.apply_rope(k, cos, sin)
        out, ck, cv = _cached_attn(
            q, k, v, ck, cv, pos, sliding_window=cfg.sliding_window,
            softmax_dtype=policy.softmax_dtype,
        )
        x = residual + linear_ops.apply_linear(lp["attn"]["o"], out.astype(x.dtype))
        residual = x
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=cfg.rms_norm_eps)
        x = residual + llama._mlp_block(lp["mlp"], hidden)
        return x, (ck, cv)

    x, (ck, cv) = jax.lax.scan(body, x, (layer_stack, cache["k"], cache["v"]))
    h = norm_ops.apply_rms_norm(params["final_norm"], x, eps=cfg.rms_norm_eps)
    logits = llama.logits_fn(params, h, cfg, policy)
    return logits[:, 0], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Mixtral / Megatron-GPT families
# ---------------------------------------------------------------------------


def prefill_mixtral(params, input_ids, cfg, policy, *, max_len=None):
    """Mixtral prefill: llama structure with the MoE MLP slot.

    ``moe_frequency > 1``: the grouped [G]-scan runs (1 MoE + f-1 dense
    llama) layers per step and re-flattens the captured KV to the flat
    ``[L, ...]`` cache layout, so ``decode_step_mixtral`` sees one uniform
    cache regardless of interleave.
    """
    from neuronx_distributed_training_tpu.models import mixtral

    if not cfg.moe.dropless:
        # capacity-factor routing computes capacity over the CURRENT batch:
        # a b-token decode step would contend for a tiny capacity and zero
        # over-capacity tokens, silently diverging from generate()
        raise NotImplementedError(
            "cached decode with dropped (capacity-factor) MoE; use dropless"
        )
    lc = cfg.llama
    s = input_ids.shape[1]
    max_len = max_len or s
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype
    )
    x = shd.constrain(x, aspec)
    cos, sin = llama._rope_for(input_ids, lc)
    layer_stack = policy.cast_to_compute(params["layers"])
    pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]

    if cfg.moe_frequency > 1:

        def gbody(x, gp):
            x, _aux, _stats, (k0, v0) = mixtral._decoder_layer(
                gp["moe"], x, cos, sin, cfg, policy, return_kv=True
            )

            def dense_body(x2, dlp):
                x2, (k, v) = llama._decoder_layer(
                    dlp, x2, cos, sin, lc, policy, return_kv=True
                )
                return x2, (k, v)

            x, (kd, vd) = jax.lax.scan(dense_body, x, gp["dense"])
            k = jnp.concatenate([k0[None], kd], axis=0)  # [f, b, s, kvh, d]
            v = jnp.concatenate([v0[None], vd], axis=0)
            return x, (jnp.pad(k, [(0, 0)] + pad), jnp.pad(v, [(0, 0)] + pad))

        x, (ck, cv) = jax.lax.scan(gbody, x, mixtral._group_xs(cfg, layer_stack))
        # [G, f, ...] -> flat [L, ...] (groups are contiguous layer runs)
        ck = ck.reshape((-1,) + ck.shape[2:])
        cv = cv.reshape((-1,) + cv.shape[2:])
    else:

        def body(x, lp):
            x, _aux, _stats, (k, v) = mixtral._decoder_layer(
                lp, x, cos, sin, cfg, policy, return_kv=True
            )
            return x, (jnp.pad(k, pad), jnp.pad(v, pad))

        x, (ck, cv) = jax.lax.scan(body, x, layer_stack)
    h = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
    return h, {"k": ck, "v": cv}


def _llama_attn_step(lp, x, ck, cv, pos, lc, policy, cos, sin):
    """Shared cached-attention sublayer for llama-structured decode bodies."""
    residual = x
    hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=lc.rms_norm_eps)
    q, k, v = _qkv(lp["attn"], hidden, lc)
    q = rope_ops.apply_rope(q, cos, sin)
    k = rope_ops.apply_rope(k, cos, sin)
    out, ck, cv = _cached_attn(
        q, k, v, ck, cv, pos, sliding_window=lc.sliding_window,
        softmax_dtype=policy.softmax_dtype,
    )
    x = residual + linear_ops.apply_linear(lp["attn"]["o"], out.astype(x.dtype))
    return x, ck, cv


def decode_step_mixtral(params, cache, tokens, pos, cfg, policy):
    from neuronx_distributed_training_tpu.models import mixtral
    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    lc = cfg.llama
    x = linear_ops.apply_embedding(
        params["embed"], tokens[:, None], compute_dtype=policy.compute_dtype
    )
    inv_freq = rope_ops.rope_frequencies(
        lc.head_size, theta=lc.rope_theta,
        position_interpolation_factor=lc.rope_interpolation_factor,
    )
    cos, sin = rope_ops.rope_cos_sin(pos[:, None], inv_freq, dtype=jnp.float32)
    layer_stack = policy.cast_to_compute(params["layers"])

    def moe_mlp(lp, x):
        residual = x
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
        hidden, _aux = moe_ops.moe_block(
            lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype
        )
        return residual + hidden

    def dense_mlp(lp, x):
        residual = x
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
        return residual + llama._mlp_block(lp["mlp"], hidden)

    if cfg.moe_frequency > 1:
        f = cfg.moe_frequency
        gk = cache["k"].reshape((-1, f) + cache["k"].shape[1:])
        gv = cache["v"].reshape((-1, f) + cache["v"].shape[1:])

        def gbody(x, inp):
            gp, ck, cv = inp  # ck/cv [f, b, max_len, kvh, d]
            x, ck0, cv0 = _llama_attn_step(
                gp["moe"], x, ck[0], cv[0], pos, lc, policy, cos, sin)
            x = moe_mlp(gp["moe"], x)

            def dense_body(x2, dinp):
                dlp, dk, dv = dinp
                x2, dk, dv = _llama_attn_step(
                    dlp, x2, dk, dv, pos, lc, policy, cos, sin)
                return dense_mlp(dlp, x2), (dk, dv)

            x, (ckd, cvd) = jax.lax.scan(dense_body, x, (gp["dense"], ck[1:], cv[1:]))
            return x, (jnp.concatenate([ck0[None], ckd], axis=0),
                       jnp.concatenate([cv0[None], cvd], axis=0))

        x, (ck, cv) = jax.lax.scan(
            gbody, x, (mixtral._group_xs(cfg, layer_stack), gk, gv))
        ck = ck.reshape((-1,) + ck.shape[2:])
        cv = cv.reshape((-1,) + cv.shape[2:])
    else:

        def body(x, inp):
            lp, ck, cv = inp
            x, ck, cv = _llama_attn_step(lp, x, ck, cv, pos, lc, policy, cos, sin)
            return moe_mlp(lp, x), (ck, cv)

        x, (ck, cv) = jax.lax.scan(body, x, (layer_stack, cache["k"], cache["v"]))
    h = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
    logits = llama.logits_fn(params, h, lc, policy)
    return logits[:, 0], {"k": ck, "v": cv}


def prefill_gpt(params, input_ids, cfg, policy, *, max_len=None):
    """Megatron-GPT prefill (learned-abs or rope, ln/rms, bias, tied head)."""
    from neuronx_distributed_training_tpu.models import gpt

    if cfg.moe is not None and not cfg.moe.dropless:
        raise NotImplementedError(
            "cached decode with dropped (capacity-factor) MoE; use dropless"
        )
    s = input_ids.shape[1]
    max_len = max_len or s
    positions = llama.positions_for(input_ids)
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype
    )
    if cfg.position_embedding_type == "learned_absolute":
        x = x + jnp.take(
            params["pos_embed"]["embedding"], positions, axis=0
        ).astype(x.dtype)
    cos, sin = gpt._rope_for(cfg, input_ids, positions=positions)
    layer_stack = policy.cast_to_compute(params["layers"])
    pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]

    if cfg.moe is not None and cfg.moe_frequency > 1:
        # grouped [G]-scan; KV re-flattened to [L, ...] (see prefill_mixtral)
        def gbody(x, gp):
            x, _aux, (k0, v0) = gpt._decoder_layer(
                cfg, gp["moe"], x, cos, sin, policy, None, return_kv=True
            )

            def dense_body(x2, dlp):
                x2, _a, (k, v) = gpt._decoder_layer(
                    cfg, dlp, x2, cos, sin, policy, None, return_kv=True
                )
                return x2, (k, v)

            x, (kd, vd) = jax.lax.scan(dense_body, x, gp["dense"])
            k = jnp.concatenate([k0[None], kd], axis=0)
            v = jnp.concatenate([v0[None], vd], axis=0)
            return x, (jnp.pad(k, [(0, 0)] + pad), jnp.pad(v, [(0, 0)] + pad))

        x, (ck, cv) = jax.lax.scan(gbody, x, gpt._group_xs(cfg, layer_stack))
        ck = ck.reshape((-1,) + ck.shape[2:])
        cv = cv.reshape((-1,) + cv.shape[2:])
    else:

        def body(x, lp):
            x, _aux, (k, v) = gpt._decoder_layer(
                cfg, lp, x, cos, sin, policy, None, return_kv=True
            )
            return x, (jnp.pad(k, pad), jnp.pad(v, pad))

        x, (ck, cv) = jax.lax.scan(body, x, layer_stack)
    h = (x if cfg.transformer_block_type == "post_ln"
         else gpt._apply_norm(cfg, params["final_norm"], x))
    return h, {"k": ck, "v": cv}


def decode_step_gpt(params, cache, tokens, pos, cfg, policy):
    from neuronx_distributed_training_tpu.models import gpt

    b = tokens.shape[0]
    nh, nkv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_size
    x = linear_ops.apply_embedding(
        params["embed"], tokens[:, None], compute_dtype=policy.compute_dtype
    )
    if cfg.position_embedding_type == "learned_absolute":
        x = x + jnp.take(
            params["pos_embed"]["embedding"], pos[:, None], axis=0
        ).astype(x.dtype)
        cos = sin = None
    else:
        rot_dim = int(cfg.head_size * cfg.rotary_percentage) // 2 * 2
        inv_freq = rope_ops.rope_frequencies(rot_dim, theta=cfg.rope_theta)
        cos, sin = rope_ops.rope_cos_sin(pos[:, None], inv_freq, dtype=jnp.float32)
    layer_stack = policy.cast_to_compute(params["layers"])

    def attn_part(lp, hidden, ck, cv):
        """Cached attention on a pre-normed (or raw, post_ln) input ->
        (o_proj output, updated cache)."""
        qkv = linear_ops.apply_linear(lp["attn"]["qkv"], hidden)
        q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
        q = q.reshape(b, 1, nh, d)
        k = k.reshape(b, 1, nkv, d)
        v = v.reshape(b, 1, nkv, d)
        if cos is not None:
            if cfg.rotary_percentage < 1.0:
                rot = int(d * cfg.rotary_percentage) // 2 * 2
                q = jnp.concatenate(
                    [rope_ops.apply_rope(q[..., :rot], cos, sin), q[..., rot:]], -1)
                k = jnp.concatenate(
                    [rope_ops.apply_rope(k[..., :rot], cos, sin), k[..., rot:]], -1)
            else:
                q = rope_ops.apply_rope(q, cos, sin)
                k = rope_ops.apply_rope(k, cos, sin)
        out, ck, cv = _cached_attn(
            q, k, v, ck, cv, pos, sliding_window=cfg.sliding_window,
            softmax_dtype=policy.softmax_dtype,
        )
        return linear_ops.apply_linear(lp["attn"]["o"], out.astype(hidden.dtype)), ck, cv

    def layer_step(lp, x, ck, cv):
        # same four layouts as gpt._decoder_layer, with cached attention
        bt = cfg.transformer_block_type
        if bt == "gpt_j":
            a, ck, cv = attn_part(lp, gpt._apply_norm(cfg, lp["input_norm"], x),
                                  ck, cv)
            m, _aux = gpt._mlp_block(
                cfg, lp["mlp"], gpt._apply_norm(cfg, lp["post_attn_norm"], x),
                policy)
            return x + a + m, ck, cv
        if bt == "post_ln":
            a, ck, cv = attn_part(lp, x, ck, cv)
            x = gpt._apply_norm(cfg, lp["input_norm"], x + a)
            m, _aux = gpt._mlp_block(cfg, lp["mlp"], x, policy)
            return gpt._apply_norm(cfg, lp["post_attn_norm"], x + m), ck, cv
        a, ck, cv = attn_part(lp, gpt._apply_norm(cfg, lp["input_norm"], x),
                              ck, cv)
        if bt == "normformer":
            a = gpt._apply_norm(cfg, lp["nf_attn_norm"], a)
        x = x + a
        m, _aux = gpt._mlp_block(
            cfg, lp["mlp"], gpt._apply_norm(cfg, lp["post_attn_norm"], x),
            policy,
            mid_norm=lp.get("nf_mlp_norm") if bt == "normformer" else None,
        )
        return x + m, ck, cv

    if cfg.moe is not None and cfg.moe_frequency > 1:
        f = cfg.moe_frequency
        gk = cache["k"].reshape((-1, f) + cache["k"].shape[1:])
        gv = cache["v"].reshape((-1, f) + cache["v"].shape[1:])

        def gbody(x, inp):
            gp, ck, cv = inp
            x, ck0, cv0 = layer_step(gp["moe"], x, ck[0], cv[0])

            def dense_body(x2, dinp):
                dlp, dk, dv = dinp
                x2, dk, dv = layer_step(dlp, x2, dk, dv)
                return x2, (dk, dv)

            x, (ckd, cvd) = jax.lax.scan(
                dense_body, x, (gp["dense"], ck[1:], cv[1:]))
            return x, (jnp.concatenate([ck0[None], ckd], axis=0),
                       jnp.concatenate([cv0[None], cvd], axis=0))

        x, (ck, cv) = jax.lax.scan(
            gbody, x, (gpt._group_xs(cfg, layer_stack), gk, gv))
        ck = ck.reshape((-1,) + ck.shape[2:])
        cv = cv.reshape((-1,) + cv.shape[2:])
    else:

        def body(x, inp):
            lp, ck, cv = inp
            x, ck, cv = layer_step(lp, x, ck, cv)
            return x, (ck, cv)

        x, (ck, cv) = jax.lax.scan(body, x, (layer_stack, cache["k"], cache["v"]))
    h = (x if cfg.transformer_block_type == "post_ln"
         else gpt._apply_norm(cfg, params["final_norm"], x))
    logits = gpt._logits_from_hidden(params, h, cfg, policy)
    return logits[:, 0], {"k": ck, "v": cv}


def generate_cached(
    params: Any,
    cfg: llama.LlamaConfig,
    policy: DtypePolicy,
    prompt_ids: jax.Array,   # [b, plen] RIGHT-padded
    prompt_lens: jax.Array,  # [b]
    *,
    max_new_tokens: int,
    eos_id: int,
    pad_id: int = 0,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """KV-cached counterpart of ``models.generate.generate`` (same contract)."""
    from neuronx_distributed_training_tpu.models.generate import filter_logits

    b, plen = prompt_ids.shape
    total = plen + max_new_tokens
    lens = prompt_lens.astype(jnp.int32)
    rows = jnp.arange(b)

    buf = jnp.full((b, total), pad_id, dtype=prompt_ids.dtype)
    buf = buf.at[:, :plen].set(prompt_ids)
    if max_new_tokens <= 0:  # same no-op contract as generate()
        return buf
    prefill_fn, decode_fn = cfg.family.decode()
    h, cache = prefill_fn(params, prompt_ids, cfg, policy, max_len=total)
    # logits ONLY at each row's last prompt position ([b, 1, h] -> [b, vocab]);
    # prefill's hidden states have the final norm already
    head_fn = cfg.family.head(cfg, policy, norm=False)
    logits = head_fn(params, h[rows, lens - 1][:, None])[:, 0]
    key = key if key is not None else jax.random.PRNGKey(0)

    def pick(next_logits, key):
        if temperature > 0:
            key, sub = jax.random.split(key)
            scaled = filter_logits(
                next_logits / temperature, top_k=top_k, top_p=top_p
            )
            return jax.random.categorical(sub, scaled, axis=-1), key
        return jnp.argmax(next_logits, axis=-1), key

    # token 0 comes from the prefill logits at each row's last prompt position
    first, key = pick(logits, key)
    first = first.astype(buf.dtype)
    buf = buf.at[rows, lens].set(first)  # the EOS itself stays visible
    done0 = first == eos_id

    def step(i, carry):
        buf, cache, done, key = carry
        pos = lens + i  # position holding the PREVIOUS token
        prev = buf[rows, pos]
        logits, cache = decode_fn(params, cache, prev, pos, cfg, policy)
        nxt, key = pick(logits, key)
        nxt = jnp.where(done, jnp.asarray(pad_id, buf.dtype), nxt.astype(buf.dtype))
        buf = buf.at[rows, pos + 1].set(nxt)
        done = done | (nxt == eos_id)
        return buf, cache, done, key

    buf, _, _, _ = jax.lax.fori_loop(
        0, max_new_tokens - 1, step, (buf, cache, done0, key)
    )
    return buf
