"""Attention ops.

``core_attention`` is the numerics-reference implementation, the counterpart of
the reference's ``CoreAttention`` (naive attention, causal mask, fp32 softmax —
``modeling_llama.py:226-251``).  ``attention`` dispatches between it and the
Pallas flash/ring kernels the same way the reference dispatches
``nki_flash_attn_func`` / ``nki_ring_attn_func`` / ``CoreAttention``
(``modeling_llama.py:482-489``), controlled by the ``fusions`` config block.

Layout is ``[batch, seq, heads, head_dim]`` throughout (the TPU-friendly layout;
the reference's ``transpose_nki_inputs`` permutation concern disappears because
Pallas block specs handle layout inside the kernel).

GQA: K/V carry ``kv_heads`` heads and are repeated to ``heads`` on the fly.
For the GSPMD core/flash paths the reference's ``kv_shared_group_size`` KV
replication trick (``modeling_llama.py:310-320``) is unnecessary — XLA
replicates KV shards from the specs when ``tp > kv_heads``.  The explicit
shard_map ring path implements the replication itself (see
``parallel.ring_attention``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import DATA_AXES


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[b, s, kv_heads, d] -> [b, s, kv_heads * n_rep, d]."""
    if n_rep == 1:
        return x
    b, s, kvh, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kvh, n_rep, d))
    return x.reshape(b, s, kvh * n_rep, d)


def causal_mask_bias(
    q_len: int,
    kv_len: int,
    *,
    q_offset: int = 0,
    sliding_window: Optional[int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Additive attention bias ``[q_len, kv_len]``: 0 where visible, large
    negative where masked.  ``q_offset`` is the absolute position of query row 0
    (used by context parallelism).  ``sliding_window`` adds the Mixtral-style
    window mask (reference ``modeling_mixtral.py:145-148``)."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = jnp.arange(kv_len)[None, :]
    visible = kv_pos <= q_pos
    if sliding_window is not None:
        visible = visible & (kv_pos > q_pos - sliding_window)
    # -10000-style finite fill like the reference (modeling_llama.py:226-251)
    # is unnecessary; use a dtype-safe large negative.
    neg = jnp.asarray(jnp.finfo(dtype).min / 2, dtype)
    return jnp.where(visible, jnp.asarray(0, dtype), neg)


def core_attention(
    q: jax.Array,  # [b, sq, h, d]
    k: jax.Array,  # [b, skv, kvh, d]
    v: jax.Array,  # [b, skv, kvh, d]
    *,
    causal: bool = True,
    q_offset: int = 0,
    sliding_window: Optional[int] = None,
    bias: Optional[jax.Array] = None,
    softmax_dtype=jnp.float32,
) -> jax.Array:
    """Naive attention with fp32 (configurable) softmax; the numerics gate for
    the Pallas kernels."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = repeat_kv(k, h // kvh)
        v = repeat_kv(v, h // kvh)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, softmax_dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=softmax_dtype)
    scores = scores.astype(softmax_dtype) * scale
    if causal:
        scores = scores + causal_mask_bias(
            sq, k.shape[1], q_offset=q_offset, sliding_window=sliding_window, dtype=softmax_dtype
        )
    if bias is not None:
        scores = scores + bias.astype(softmax_dtype)
    # Tag the O(s^2) internals so the "selective" remat policy recomputes them
    # in backward instead of saving them (the reference's
    # activations_checkpoint_recompute: [CoreAttention]).
    scores = checkpoint_name(scores, "attn_scores")
    probs = jax.nn.softmax(scores, axis=-1)
    probs = checkpoint_name(probs, "attn_probs")
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def padding_mask_bias(attention_mask: jax.Array, dtype=jnp.float32) -> jax.Array:
    """``attention_mask`` [b, skv] (1 = real token) -> additive bias
    [b, 1, 1, skv] masking padded KEYS (the HF contract; reference
    ``llama_model.py:94-101`` includes ``attention_mask`` in input_names)."""
    neg = jnp.asarray(jnp.finfo(dtype).min / 2, dtype)
    bias = jnp.where(attention_mask.astype(bool), jnp.asarray(0, dtype), neg)
    return bias[:, None, None, :]


def segment_mask_bias(segment_ids: jax.Array, dtype=jnp.float32) -> jax.Array:
    """``segment_ids`` [b, s] -> additive bias [b, 1, s, s] restricting
    attention to same-segment (packed-record) pairs — the numerics reference
    for the flash kernel's block-diagonal segment mask."""
    neg = jnp.asarray(jnp.finfo(dtype).min / 2, dtype)
    same = segment_ids[:, :, None] == segment_ids[:, None, :]
    return jnp.where(same, jnp.asarray(0, dtype), neg)[:, None, :, :]


def _flash_on_mesh(q, k, v, attention_mask, segment_ids, **kw):
    """``flash_attention`` made legal on a mesh.  The Pallas kernel is a
    Mosaic custom call, which GSPMD refuses to partition while any mesh axis
    is automatic, so with a mesh active the call is made per shard inside a
    fully-manual ``shard_map``: batch over ``data``/``expert``, heads over
    ``model``, replicated over the rest.  Attention needs nothing from
    another batch row or head, so the body holds no collective.  When
    tp exceeds the KV heads they are repeated until each rank owns one (the
    rule ``parallel.ulysses`` uses), keeping q/kv head groups aligned."""
    from neuronx_distributed_training_tpu.ops.flash_attention import flash_attention

    mesh, manual = shd.region_mesh()
    if mesh is None:
        return flash_attention(
            q, k, v, attention_mask=attention_mask, segment_ids=segment_ids, **kw
        )
    tp = int(mesh.shape.get("model", 1))
    nh, nkv = q.shape[2], k.shape[2]
    if nh % tp or (nkv % tp and tp % nkv):
        raise ValueError(
            f"flash attention on a mesh: tp={tp} must divide num_heads={nh}, "
            f"and kv_heads={nkv} and tp must divide one another"
        )
    if nkv % tp:
        k, v = repeat_kv(k, tp // nkv), repeat_kv(v, tp // nkv)
    heads = P(DATA_AXES, None, "model", None)
    rows = P(DATA_AXES, None)
    extras = {"attention_mask": attention_mask, "segment_ids": segment_ids}
    names = [n for n, x in extras.items() if x is not None]

    def body(q, k, v, *row_args):
        return flash_attention(q, k, v, **dict(zip(names, row_args)), **kw)

    # inside a manual region (the pipeline body, manual over ``pipe``) take
    # the remaining axes manual too; otherwise every axis of ``mesh``
    axis_names = frozenset(mesh.axis_names) - manual if manual else frozenset()
    fn = shd.shard_map(
        body,
        mesh=mesh,
        in_specs=(heads, heads, heads) + (rows,) * len(names),
        out_specs=heads,
        axis_names=axis_names,
        check_vma=False,
    )
    return fn(q, k, v, *(extras[n] for n in names))


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "core",  # "core" | "flash" | "ring" | "ulysses"
    causal: bool = True,
    q_offset: int = 0,
    sliding_window: Optional[int] = None,
    softmax_dtype=jnp.float32,
    attention_mask: Optional[jax.Array] = None,  # [b, skv] 1 = attend
    segment_ids: Optional[jax.Array] = None,  # [b, s] packed-record segments
    block_q: Optional[int] = None,   # Pallas flash tile sizes (None = default;
    block_kv: Optional[int] = None,  # a per-chip tuning knob, fusions.flash_block_*)
    keep_flash_outputs: bool = False,  # the caller's remat policy keeps them
) -> jax.Array:
    """Dispatch mirroring the reference's flash/ring/Core selection
    (``modeling_llama.py:482-489``).

    ``attention_mask`` (padded KEYS, the HF contract) is supported in-kernel
    by the flash, ring, ulysses, and core paths — padded SFT/DPO batches stay
    on the O(seq)-memory kernels (the reference runs its NKI flash kernel on
    ``attention_mask`` batches too, ``llama_model.py:94-101``).  Only
    zigzag_ring rejects it: the batch is zig-zag permuted and a key-position
    mask would be wrong in that layout.

    ``keep_flash_outputs``: the layer is rematerialized under a policy that
    keeps the flash forward kernel's outputs (``models.llama._remat_policy``,
    ``full``), so the ``flash`` path names them (``flash_attention``'s
    ``keep_outputs``).  The context-parallel bodies take no notice: a ring
    would keep one partial output a step."""
    if attention_mask is not None and impl == "zigzag_ring":
        # a core fallback would be WRONG here (the batch is zig-zag permuted
        # and core's causal mask assumes contiguous order) — so raise
        raise ValueError(
            "zigzag_ring does not support attention_mask (padded batches); "
            "use fusions.ring_attention"
        )
    if segment_ids is not None and impl in ("ring", "ulysses", "zigzag_ring"):
        # the CP bodies don't implement the block-diagonal segment mask;
        # a silent core fallback would defeat the CP memory bound — raise
        raise ValueError(
            f"segment_ids (packed-sequence masking) is supported by the "
            f"flash and core paths only, not {impl!r}"
        )
    if impl == "flash":
        return _flash_on_mesh(
            q, k, v, attention_mask, segment_ids, causal=causal,
            sliding_window=sliding_window, q_offset=q_offset,
            block_q=block_q, block_kv=block_kv, keep_outputs=keep_flash_outputs,
        )
    if impl == "ring":
        from neuronx_distributed_training_tpu.parallel.ring_attention import ring_attention

        if q_offset:
            raise ValueError(
                "ring attention derives global positions from the mesh; "
                "an explicit q_offset is not meaningful here"
            )
        return ring_attention(
            q, k, v, causal=causal, sliding_window=sliding_window,
            block_kv=block_kv or 512, attention_mask=attention_mask,
        )
    if impl == "ulysses":
        from neuronx_distributed_training_tpu.parallel.ulysses import ulysses_attention

        if q_offset:
            raise ValueError(
                "ulysses attention derives global positions from the mesh; "
                "an explicit q_offset is not meaningful here"
            )
        return ulysses_attention(
            q, k, v, causal=causal, sliding_window=sliding_window,
            block_kv=block_kv or 512, attention_mask=attention_mask,
        )
    if impl == "zigzag_ring":
        from neuronx_distributed_training_tpu.parallel.ring_attention import (
            zigzag_ring_attention,
        )

        if q_offset:
            raise ValueError(
                "zigzag ring derives positions from the layout; an explicit "
                "q_offset is not meaningful here"
            )
        if sliding_window is not None:
            raise ValueError(
                "zigzag ring does not support sliding_window; use "
                "ring_attention (contiguous layout) for windowed models"
            )
        return zigzag_ring_attention(q, k, v, causal=causal)
    bias = None
    if attention_mask is not None:
        bias = padding_mask_bias(attention_mask, softmax_dtype)
    if segment_ids is not None:
        seg_bias = segment_mask_bias(segment_ids, softmax_dtype)
        bias = seg_bias if bias is None else bias + seg_bias
    return core_attention(
        q,
        k,
        v,
        causal=causal,
        q_offset=q_offset,
        sliding_window=sliding_window,
        bias=bias,
        softmax_dtype=softmax_dtype,
    )
