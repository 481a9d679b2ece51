"""Ring attention — context-parallel attention over the ``context`` mesh axis.

The TPU-native replacement for the reference's NKI ring-attention kernel
(``neuronx_distributed.kernels.ring_attention_kernel``, called at reference
``modeling_llama.py:71,484`` with explicit CP src/tgt ring pairs).  Design:

- the sequence is sharded over the ``context`` axis; each rank holds local
  Q/K/V chunks ``[b, s/cp, h, d]``;
- a ``lax.scan`` performs ``cp`` ring steps: attend local Q to the currently
  held KV chunk, then rotate K/V to the next rank with ``lax.ppermute`` over
  ICI (the reference's ``get_context_model_parallel_src_tgt_pairs`` ring);
- partial results merge with the online-softmax (m, l, acc) recurrence in fp32
  — mathematically identical to flash attention's block accumulation, so the
  result matches full-sequence attention to numerical precision;
- the whole thing is plain differentiable JAX (``ppermute`` transposes to the
  reverse ring, ``scan`` reverses): no hand-written backward.  The per-chunk
  score/prob tensors are rematerialized in backward (``jax.checkpoint``), so
  memory stays O(s/cp * s/cp) per step like the reference kernel — this is
  what makes CP long-context viable.

The public ``ring_attention`` wraps the per-rank body in ``shard_map`` over the
active mesh: batch over ``(data, expert)``, heads over ``model``, sequence over
``context``.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.parallel.mesh import DATA_AXES
from neuronx_distributed_training_tpu.parallel import sharding as shd

NEG_INF = -1e30

logger = logging.getLogger(__name__)
_warned_bkv: set = set()


def _block_update(qh, ks, vs, o_acc, m_acc, l_acc, q_off, kv_off, *, scale,
                  causal, window, kv_mask=None):
    """One online-softmax accumulation against a KV BLOCK (ks, vs).

    qh [b, h, sq, d]; ks/vs [b, h, bkv, d] (GQA heads already repeated);
    o_acc [b, h, sq, d]; m_acc/l_acc [b, h, sq, 1].  Offsets are traced
    scalars (global positions of query row 0 / kv row 0).  ``kv_mask``
    [b, bkv] (1 = real key) masks padded keys.
    """
    s = jax.lax.dot_general(
        qh, ks, (((3,), (3,)), ((0, 1), (0, 1))), preferred_element_type=jnp.float32
    ) * scale  # [b, h, sq, bkv]
    sq, bkv = s.shape[-2], s.shape[-1]
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 0)
    kv_pos = kv_off + jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 1)
    if causal:
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
    if window is not None:
        # Mixtral-style sliding window on GLOBAL positions (reference
        # modeling_mixtral.py:145-148); composes with the ring offsets
        s = jnp.where(kv_pos > q_pos - window, s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
    m_c = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_acc, m_c)
    alpha = jnp.exp(m_acc - m_new)  # rescale of previous partials
    p = jnp.exp(s - m_new)
    l_new = alpha * l_acc + jnp.sum(p, axis=-1, keepdims=True)
    o_new = alpha * o_acc + jax.lax.dot_general(
        p.astype(vs.dtype), vs, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def _chunk_update(q, kc, vc, o_acc, m_acc, l_acc, q_off, kv_off, *, scale,
                  causal, window, block_kv, kv_mask=None):
    """Accumulate one ring chunk BLOCKWISE over its KV length.

    The fp32 score tensor is [b, h, sq, block_kv] per inner step instead of
    [b, h, sq, s/cp] — this is what keeps 32k-sequence CP inside single-chip
    memory (flash attention's tiling, expressed in XLA; the Pallas kernel is
    the single-chip fast path, this is the ring body).
    q [b, h, sq, d]; kc/vc [b, kvh, skv, d] (un-repeated GQA heads — repeated
    here, inside the remat boundary, so the ring rotates and the scan carries
    only kvh heads).  ``kv_mask`` [b, skv] (1 = real key) masks padded keys.
    """
    h, kvh = q.shape[1], kc.shape[1]
    if kvh != h:
        kc = jnp.repeat(kc, h // kvh, axis=1)
        vc = jnp.repeat(vc, h // kvh, axis=1)
    skv = kc.shape[2]
    bkv = min(block_kv, skv)
    if skv % bkv:
        bkv = skv  # non-divisible chunk: single block (tiny cases only)
    n_blocks = skv // bkv

    if n_blocks == 1:
        return _block_update(q, kc, vc, o_acc, m_acc, l_acc, q_off, kv_off,
                             scale=scale, causal=causal, window=window,
                             kv_mask=kv_mask)

    def blk(carry, i):
        o, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(kc, i * bkv, bkv, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vc, i * bkv, bkv, axis=2)
        ms = (None if kv_mask is None
              else jax.lax.dynamic_slice_in_dim(kv_mask, i * bkv, bkv, axis=1))
        o, m, l = _block_update(q, ks, vs, o, m, l, q_off, kv_off + i * bkv,
                                scale=scale, causal=causal, window=window,
                                kv_mask=ms)
        return (o, m, l), None

    (o_acc, m_acc, l_acc), _ = jax.lax.scan(
        blk, (o_acc, m_acc, l_acc), jnp.arange(n_blocks)
    )
    return o_acc, m_acc, l_acc


def _merge_partial(o_acc, lse_acc, o_c, lse_c):
    """Online merge of a normalized partial attention result.

    ``(o_acc [b,h,sq,d] fp32, lse_acc [b,h,sq])`` += chunk ``(o_c, lse_c)``:
    ``o = sum_i o_i * exp(lse_i - lse)``, ``lse = logaddexp_i lse_i`` — exact
    softmax recombination; fully-masked chunks carry ``lse_c = NEG_INF`` and
    drop out via the where-guarded weights (``exp(NEG_INF - NEG_INF)`` must
    not become 1).
    """
    lse_new = jnp.maximum(lse_acc, lse_c) + jnp.log1p(
        jnp.exp(-jnp.abs(lse_acc - lse_c))
    )
    lse_new = jnp.where(
        jnp.maximum(lse_acc, lse_c) > NEG_INF / 2, lse_new, NEG_INF
    )
    w_prev = jnp.where(lse_acc > NEG_INF / 2, jnp.exp(lse_acc - lse_new), 0.0)
    w_c = jnp.where(lse_c > NEG_INF / 2, jnp.exp(lse_c - lse_new), 0.0)
    o_new = o_acc * w_prev[..., None] + o_c.astype(jnp.float32) * w_c[..., None]
    return o_new, lse_new


def _ring_local_flash(q, k, v, kvm=None, *, axis_name, cp, causal, window,
                      interpret):
    """Per-rank ring body fused with the Pallas flash kernel.

    q [b, sq, h, d]; k/v [b, skv, kvh, d]; kvm None or [b, skv] (local key
    padding mask chunk, rotated with K/V) -> o [b, sq, h, d].

    The ring is unrolled over the (static) step index ``t`` so the kernel's
    block-masking offsets stay trace-time constants: at ``t == 0`` the held
    chunk is the rank's own (diagonal — causal mask, offset 0); at ``t > 0``
    the chunk ``src = my - t (mod cp)`` is either entirely in the past
    (``my >= t`` — no mask, relative offset ``t*sq``) or entirely in the
    future (contribution dropped by zeroing its merge weight).  The wasted
    future-chunk compute is the standard causal-ring imbalance (zig-zag
    sharding would fix it; the reference's ring kernel has the same property).
    """
    b, sq, h, d = q.shape
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    from neuronx_distributed_training_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )

    o_acc = jnp.zeros((b, h, sq, d), jnp.float32)
    lse_acc = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    kc, vc, mc = k, v, kvm
    for t in range(cp):
        if not causal:
            o_c, lse_c = flash_attention_with_lse(
                q, kc, vc, causal=False, attention_mask=mc, interpret=interpret
            )
        elif t == 0:
            o_c, lse_c = flash_attention_with_lse(
                q, kc, vc, causal=True, sliding_window=window, q_offset=0,
                attention_mask=mc, interpret=interpret,
            )
        else:
            # past chunk: fully causally visible; only the sliding window (if
            # any) masks, with static relative offset t*sq
            o_c, lse_c = flash_attention_with_lse(
                q, kc, vc, causal=False, sliding_window=window,
                q_offset=t * sq, attention_mask=mc, interpret=interpret,
            ) if window is not None else flash_attention_with_lse(
                q, kc, vc, causal=False, attention_mask=mc, interpret=interpret
            )
            lse_c = jnp.where(my >= t, lse_c, NEG_INF)
        o_acc, lse_acc = _merge_partial(
            o_acc, lse_acc, jnp.swapaxes(o_c, 1, 2), lse_c
        )
        if t < cp - 1:
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            if mc is not None:
                mc = jax.lax.ppermute(mc, axis_name, perm)
    o = jnp.where(lse_acc[..., None] > NEG_INF / 2, o_acc, 0.0)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _ring_local(q, k, v, kvm=None, *, axis_name, cp, causal, window, block_kv):
    """Per-rank ring attention body (runs inside shard_map).

    q [b, sq, h, d]; k/v [b, skv, kvh, d] (local chunks); kvm None or
    [b, skv] (local key padding mask, rotated with K/V) -> o [b, sq, h, d].
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    my = jax.lax.axis_index(axis_name)
    q_off = my * sq
    scale = 1.0 / (d ** 0.5)

    # head-major layout for the inner matmuls
    qh = jnp.swapaxes(q, 1, 2)  # [b, h, sq, d]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)

    perm = [(i, (i + 1) % cp) for i in range(cp)]
    compute = jax.checkpoint(
        functools.partial(_chunk_update, scale=scale, causal=causal,
                          window=window, block_kv=block_kv)
    )

    def step(carry, t):
        o_acc, m_acc, l_acc, kc, vc, mc = carry
        src = jax.lax.rem(my - t + cp, cp)  # rank whose chunk we currently hold
        o_acc, m_acc, l_acc = compute(
            qh, kc, vc, o_acc, m_acc, l_acc, q_off, src * skv, kv_mask=mc
        )
        # rotate KV around the ring (skipped result unused on last step, but
        # keeping it unconditional keeps the collective schedule uniform)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if mc is not None:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        return (o_acc, m_acc, l_acc, kc, vc, mc), None

    (o_acc, m_acc, l_acc, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, kh, vh, kvm), jnp.arange(cp)
    )
    # causal: every row sees at least itself at t=0, so l > 0; guard anyway
    l_safe = jnp.where(l_acc == 0.0, 1.0, l_acc)
    o = jnp.where(m_acc > NEG_INF / 2, o_acc / l_safe, 0.0)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)  # [b, sq, h, d]


def in_manual_region() -> bool:
    """True inside a ``shard_map`` Manual region (e.g. the pipeline body).

    A nested inner ``shard_map`` mishandles data that VARIES over the outer
    manual axis under ``check_vma=False``: the forward is right but the
    backward sums cotangents across the outer axis (verified: pipe-varying
    inputs through a nested ring produce corrupted dq/dk/dv while loss stays
    exact).  CP attention therefore must NOT open an inner shard_map there —
    callers switch to the pure-GSPMD blockwise body instead.
    """
    return bool(shd.manual_axes())


def pick_bkv(s: int, block_kv: int) -> tuple[int, bool]:
    """Largest divisor of ``s`` no bigger than ``block_kv``, and whether the
    choice is degraded (>8x smaller than asked — an s/bkv-step scan).  Shared
    by ``blockwise_gspmd_attention`` and the config-validation catalog so the
    load-time rejection can never drift from the trace-time selection."""
    bkv = max(1, min(block_kv, s))
    while s % bkv:
        bkv -= 1
    return bkv, bkv * 8 < min(block_kv, s)


def blockwise_gspmd_attention(q, k, v, *, causal=True, sliding_window=None,
                              block_kv: int = 512, attention_mask=None):
    """Memory-bounded global attention with NO explicit collectives.

    The online-softmax block scan of ``_chunk_update`` applied to the FULL
    (GSPMD-global) sequence: XLA partitions the seq-sharded operands and
    inserts the context-axis collectives itself, so this is correct under any
    enclosing manual region (the nested-shard_map backward hazard above).
    It is the CP-attention body used under pipeline parallelism — the
    explicit ppermute ring (faster comm schedule) is the pp == 1 fast path.
    Score memory stays O(sq x block_kv) like the ring body.
    ``attention_mask`` [b, s] (1 = real key) masks padded keys in-scan.
    """
    b, s, h, d = q.shape
    # largest divisor of s <= block_kv: _chunk_update's non-divisible
    # fallback collapses to ONE block, which at the full global sequence
    # would be an O(s^2) score tensor — exactly what this body must bound
    bkv, degraded = pick_bkv(s, block_kv)
    if degraded and (s, block_kv) not in _warned_bkv:
        # a non-smooth sequence length (e.g. prime s) degrades to a tiny bkv
        # and an s/bkv-step scan with pathological compile/step time — make
        # the cliff loud instead of silent (ADVICE r2), once per shape
        _warned_bkv.add((s, block_kv))
        logger.warning(
            "blockwise_gspmd_attention: seq %d has no divisor near block_kv "
            "%d (chose %d) — the %d-step scan will be slow; pad the sequence "
            "to a smoother length", s, block_kv, bkv, s // bkv,
        )
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s, 1), jnp.float32)
    compute = jax.checkpoint(functools.partial(
        _chunk_update, scale=1.0 / (d ** 0.5), causal=causal,
        window=sliding_window, block_kv=bkv,
    ))
    kvm = None if attention_mask is None else attention_mask.astype(jnp.int32)
    o, m, l = compute(qh, kh, vh, o0, m0, l0, 0, 0, kv_mask=kvm)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.where(m > NEG_INF / 2, o / l_safe, 0.0)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _cp_prep(q, k, v, *, axis_name, mesh, tag):
    """Shared CP-attention scaffolding: resolve mesh/cp/tp, validate head
    divisibility, apply the GQA KV replication for ``tp > kv_heads`` (the
    reference's ``kv_shared_group_size`` trick, ``modeling_llama.py:310-320``
    — consecutive ``jnp.repeat`` so TP rank ``r`` holds exactly the KV head
    its Q heads attend to; gradient accumulation over the sharing ranks is
    XLA's job), and build the shard_map spec.

    Returns ``None`` when cp == 1 (caller falls back to core attention), else
    ``(mesh, cp, tp, k, v, q_spec, h_l, kvh_l)``.  When cp > 1 inside a
    Manual region (``in_manual_region()``) callers must NOT open the inner
    shard_map — ring routes to ``blockwise_gspmd_attention``, zigzag raises.
    """
    mesh = mesh or shd.active_mesh()
    cp = int(mesh.shape.get(axis_name, 1)) if mesh is not None else 1
    if cp == 1:
        return None
    h, kvh = q.shape[2], k.shape[2]
    tp = int(mesh.shape.get("model", 1))
    if tp > 1:
        if h % tp != 0:
            raise ValueError(
                f"{tag}: num_heads {h} must be divisible by tp {tp}"
            )
        if kvh % tp != 0:
            if tp % kvh != 0:
                raise ValueError(
                    f"{tag}: kv_heads {kvh} and tp {tp} must divide "
                    f"one another (got kvh%tp and tp%kvh both nonzero)"
                )
            mult = tp // kvh
            k = jnp.repeat(k, mult, axis=2)
            v = jnp.repeat(v, mult, axis=2)
    q_spec = P(DATA_AXES, "context", "model" if tp > 1 else None, None)
    h_l = h // tp if tp > 1 else h
    kvh_eff = k.shape[2]  # after any tp>kvh replication above
    kvh_l = kvh_eff // tp if tp > 1 else kvh_eff
    return mesh, cp, tp, k, v, q_spec, h_l, kvh_l


def ring_attention(
    q: jax.Array,  # [b, s, h, d]  (seq sharded over "context" under GSPMD)
    k: jax.Array,  # [b, s, kvh, d]
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    axis_name: str = "context",
    mesh=None,
    block_kv: int = 512,
    attention_mask: Optional[jax.Array] = None,  # [b, s] 1 = real key
) -> jax.Array:
    """Context-parallel ring attention over the active mesh.

    Falls back to ``core_attention`` when no mesh is active or cp == 1 (so the
    same model code runs in unit tests and CP-off configs), matching the
    dispatch contract of ``ops.attention``.

    GQA with ``tp > kv_heads``: KV heads are replicated ``tp / kv_heads``
    times (consecutively, so TP rank ``r`` holds exactly the KV head its Q
    heads attend to) — the reference's ``kv_shared_group_size`` /
    ``GQAQKVColumnParallelLinear(kv_size_multiplier=...)`` trick
    (``modeling_llama.py:310-320``, ``config_overview.rst:403-409``).  The
    replication is a GSPMD-level ``jnp.repeat`` so gradient accumulation over
    the sharing TP ranks is XLA's job.
    """
    if not causal:
        # the window is a causal-attention concept everywhere in this stack
        # (core_attention applies it inside the causal mask; flash_attention
        # drops it when causal=False) — match that contract here
        sliding_window = None
    mesh_ = mesh or shd.active_mesh()
    cp_ = int(mesh_.shape.get(axis_name, 1)) if mesh_ is not None else 1
    if cp_ > 1 and in_manual_region():
        # pipeline body (Manual over pipe): the GSPMD blockwise body — the
        # reference's TP x PP x CP flagship layout
        # (hf_llama3_70B_CP_config.yaml) runs through here
        return blockwise_gspmd_attention(
            q, k, v, causal=causal, sliding_window=sliding_window,
            block_kv=block_kv, attention_mask=attention_mask,
        )
    prep = _cp_prep(q, k, v, axis_name=axis_name, mesh=mesh, tag="ring attention")
    if prep is None:
        from neuronx_distributed_training_tpu.ops.attention import (
            core_attention,
            padding_mask_bias,
        )

        return core_attention(
            q, k, v, causal=causal, sliding_window=sliding_window,
            bias=(None if attention_mask is None
                  else padding_mask_bias(attention_mask)),
        )
    mesh, cp, tp, k, v, q_spec, h_l, kvh_l = prep

    # fuse the Pallas flash kernel into the ring body when the local shapes
    # tile (VERDICT r1: the ring step should be the flash kernel, not XLA
    # blockwise); tiny/odd shapes keep the XLA blockwise body
    from neuronx_distributed_training_tpu.ops.flash_attention import flash_tileable

    s, d = q.shape[1], q.shape[3]
    sq_l = s // cp
    if flash_tileable(sq_l, sq_l, d, max(h_l, 1), max(kvh_l, 1)):
        body = functools.partial(
            _ring_local_flash, axis_name=axis_name, cp=cp, causal=causal,
            window=sliding_window, interpret=None,
        )
    else:
        body = functools.partial(
            _ring_local, axis_name=axis_name, cp=cp, causal=causal,
            window=sliding_window, block_kv=block_kv,
        )
    extra_specs, extra_args = (), ()
    if attention_mask is not None:
        extra_specs = (P(DATA_AXES, "context"),)
        extra_args = (attention_mask.astype(jnp.int32),)
    fn = shd.shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec) + extra_specs,
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v, *extra_args)


# ---------------------------------------------------------------------------
# zig-zag layout — balanced causal ring (not in the reference)
# ---------------------------------------------------------------------------


def zigzag_positions(s: int, cp: int) -> jnp.ndarray:
    """Original position of each token slot in the zig-zag layout ``[s]``.

    The sequence splits into ``2*cp`` chunks; CP rank ``r`` holds chunks
    ``(r, 2cp-1-r)``.  Contiguous causal rings are imbalanced — rank 0's chunk
    is visible to nothing it holds while rank ``cp-1`` attends everything
    (the "causal-ring imbalance" noted on ``_ring_local_flash``); pairing the
    ``r``-th-lowest with the ``r``-th-highest chunk gives every rank the same
    causal work per ring step.  The reference has no equivalent (its NKI ring
    kernel is contiguous).

    Returns ``pos`` with ``pos[p]`` = original position of the token stored at
    layout slot ``p`` (slots are contiguous per rank under the usual
    ``P(..., "context", ...)`` sharding).  ``cp == 1`` is the identity.
    """
    if s % (2 * cp) != 0:
        raise ValueError(f"zigzag: seq {s} must divide by 2*cp = {2 * cp}")
    hc = s // (2 * cp)
    idx = []
    for r in range(cp):
        idx.append(jnp.arange(r * hc, (r + 1) * hc))
        idx.append(jnp.arange((2 * cp - 1 - r) * hc, (2 * cp - r) * hc))
    return jnp.concatenate(idx)


def zigzag_transform_batch(batch: dict, cp: int) -> dict:
    """Permute a causal-LM batch into the zig-zag layout.

    Labels are shifted to next-token targets in the ORIGINAL order first (the
    in-model shift is order-dependent and must be disabled —
    ``shift_labels=False``), then every per-token array is gathered through
    the permutation.  Gathering a seq-sharded batch is a cross-rank permute of
    ids/labels only (a few bytes per token, once per step).
    """
    ids = batch["input_ids"]
    s = ids.shape[1]
    pos = zigzag_positions(s, cp)
    labels = batch.get("labels", ids)
    loss_mask = batch.get("loss_mask")
    # next-token shift in original order (ce_ops.shift_for_next_token
    # semantics: target[i] = labels[i+1], final slot masked out)
    pad = jnp.full(labels.shape[:1] + (1,), -100, labels.dtype)
    tgt = jnp.concatenate([labels[:, 1:], pad], axis=1)
    if loss_mask is not None:
        mpad = jnp.zeros(loss_mask.shape[:1] + (1,), loss_mask.dtype)
        loss_mask = jnp.concatenate([loss_mask[:, 1:], mpad], axis=1)
    out = dict(batch)
    out["input_ids"] = jnp.take(ids, pos, axis=1)
    out["labels"] = jnp.take(tgt, pos, axis=1)
    if loss_mask is not None:
        out["loss_mask"] = jnp.take(loss_mask, pos, axis=1)
    return out


def _pair_attn(qh, kh, vh, *, diag, use_flash, interpret=None):
    """One (q half-chunk, kv half-chunk) attention -> normalized (o, lse).

    ``diag=True``: same chunk, plain causal.  ``diag=False``: kv chunk is
    entirely in the q chunk's past — no mask.  q/k/v are [b, hc, heads, d];
    returns (o [b, h, hc, d] fp32, lse [b, h, hc]).
    """
    if use_flash:
        from neuronx_distributed_training_tpu.ops.flash_attention import (
            flash_attention_with_lse,
        )

        o, lse = flash_attention_with_lse(
            qh, kh, vh, causal=diag, interpret=interpret
        )
        return jnp.swapaxes(o, 1, 2).astype(jnp.float32), lse
    b, hc, h, d = qh.shape
    q_t = jnp.swapaxes(qh, 1, 2)
    o0 = jnp.zeros((b, h, hc, d), jnp.float32)
    m0 = jnp.full((b, h, hc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, hc, 1), jnp.float32)
    # remat the O(hc^2) scores in backward — same memory class as _ring_local
    compute = jax.checkpoint(functools.partial(
        _chunk_update, scale=1.0 / (d ** 0.5), causal=diag, window=None,
        block_kv=hc,
    ))
    o, m, l = compute(
        q_t, jnp.swapaxes(kh, 1, 2), jnp.swapaxes(vh, 1, 2), o0, m0, l0, 0, 0,
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = jnp.where(m > NEG_INF / 2, m + jnp.log(l_safe), NEG_INF)[..., 0]
    return o / l_safe, lse


def _zigzag_local(q, k, v, *, axis_name, cp, use_flash):
    """Per-rank zig-zag ring body (inside shard_map).

    q [b, 2*hc, h, d]: the rank's chunks (a=my, b=2cp-1-my) back to back.
    Ring over KV like the contiguous body; every (q half, kv half) pair is one
    of three STATIC mask cases — kv chunk < q chunk: no mask; ==: plain
    causal; >: skipped — selected per pair with ``lax.switch`` on the traced
    chunk ids, so each rank executes exactly ``2*cp + 1`` visible pairs
    regardless of rank index (the balance property).
    """
    b, s2, h, d = q.shape
    hc = s2 // 2
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def pair(qh, kh, vh, qc, kc):
        def full(_):
            return _pair_attn(qh, kh, vh, diag=False, use_flash=use_flash)

        def diag(_):
            return _pair_attn(qh, kh, vh, diag=True, use_flash=use_flash)

        def skip(_):
            return (jnp.zeros((b, h, hc, d), jnp.float32),
                    jnp.full((b, h, hc), NEG_INF, jnp.float32))

        sel = jnp.where(kc < qc, 0, jnp.where(kc == qc, 1, 2))
        return jax.lax.switch(sel, [full, diag, skip], None)

    o_acc = jnp.zeros((b, 2, h, hc, d), jnp.float32)  # per q half
    lse_acc = jnp.full((b, 2, h, hc), NEG_INF, jnp.float32)
    kc_, vc_ = k, v
    q_halves = (q[:, :hc], q[:, hc:])
    for t in range(cp):
        src = jax.lax.rem(my - t + cp, cp)
        held_chunks = (src, 2 * cp - 1 - src)
        my_chunks = (my, 2 * cp - 1 - my)
        for qi in range(2):
            for ki in range(2):
                o_c, lse_c = pair(
                    q_halves[qi], kc_[:, ki * hc:(ki + 1) * hc],
                    vc_[:, ki * hc:(ki + 1) * hc],
                    my_chunks[qi], held_chunks[ki],
                )
                o_new, lse_new = _merge_partial(
                    o_acc[:, qi], lse_acc[:, qi], o_c, lse_c
                )
                o_acc = o_acc.at[:, qi].set(o_new)
                lse_acc = lse_acc.at[:, qi].set(lse_new)
        if t < cp - 1:
            kc_ = jax.lax.ppermute(kc_, axis_name, perm)
            vc_ = jax.lax.ppermute(vc_, axis_name, perm)
    o = jnp.where(lse_acc[..., None] > NEG_INF / 2, o_acc, 0.0)
    # [b, 2, h, hc, d] -> [b, 2*hc, h, d]
    o = jnp.swapaxes(o, 2, 3).reshape(b, s2, h, d)
    return o.astype(q.dtype)


def zigzag_ring_attention(
    q: jax.Array,  # [b, s, h, d] in the ZIG-ZAG layout, seq over "context"
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    axis_name: str = "context",
    mesh=None,
) -> jax.Array:
    """Balanced causal ring attention over the zig-zag layout.

    Inputs must already be in the layout ``zigzag_positions`` describes (the
    trainer permutes the batch via ``zigzag_transform_batch`` and feeds the
    model matching RoPE positions).  cp == 1 is the identity layout, so the
    fallback is plain core attention — same dispatch contract as the ring.
    Causal only: non-causal rings have no imbalance to fix.
    """
    if not causal:
        raise ValueError("zigzag ring is causal-only; use ring_attention")
    prep = _cp_prep(q, k, v, axis_name=axis_name, mesh=mesh, tag="zigzag ring")
    if prep is None:
        from neuronx_distributed_training_tpu.ops.attention import core_attention

        return core_attention(q, k, v, causal=True)
    if in_manual_region():
        # the zig-zag layout's mask cases assume the explicit ring; inside a
        # manual region the trainer's pp guard should have fired already
        raise ValueError(
            "zigzag ring cannot run inside a manual (pipeline) region; use "
            "fusions.ring_attention for pp + cp configs"
        )
    mesh, cp, tp, k, v, q_spec, h_l, kvh_l = prep

    s, d = q.shape[1], q.shape[3]
    if s % (2 * cp) != 0:
        raise ValueError(f"zigzag ring: seq {s} must divide by 2*cp = {2 * cp}")
    from neuronx_distributed_training_tpu.ops.flash_attention import flash_tileable

    hc = s // (2 * cp)
    use_flash = flash_tileable(hc, hc, d, max(h_l, 1), max(kvh_l, 1))

    fn = shd.shard_map(
        functools.partial(_zigzag_local, axis_name=axis_name, cp=cp,
                          use_flash=use_flash),
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v)
