"""Pallas flash attention (fwd + custom-vjp bwd) for TPU.

The TPU-native replacement for the reference's NKI flash-attention kernel
(``neuronx_distributed.kernels.flash_attn``, called at reference
``modeling_llama.py:70,486`` behind the ``fusions.flash_attention`` YAML flag).
Online-softmax blockwise attention: O(seq) memory instead of the O(seq^2)
score/prob materialization of ``core_attention``, with the backward pass
recomputing probabilities per block (no saved probs at all — strictly better
than the reference's "selective recompute of CoreAttention").

Design notes (see /opt/skills/guides/pallas_guide.md):
- grid = (batch, q_heads, q_blocks, kv_band); the kv dimension is innermost
  and sequential ("arbitrary"), carrying the online-softmax state (m, l, acc)
  in VMEM scratch across kv steps.
- the band: the innermost dimension covers only the blocks that ``causal``,
  ``window`` and ``q_offset`` can show an outer block (``_band``, at trace
  time from Python ints): for fwd and dq the key blocks from a query block's
  first to its last visible one, for dkv the query blocks that see a key
  block; its extent is the longest such span (3 of 16 key blocks and 12 of 64
  query blocks at seq 32768, tiles 512 x 2048, window 4096; the whole range
  without a window or with 2 key blocks).  Index maps and kernel bodies turn
  the step ``j`` into the absolute block ``first(outer) + j`` by integer
  arithmetic on the program ids (``_walk``).  A block with fewer partners
  than the band (the first windows, the sequence's ends, a ring step's chunk
  that shows a query block nothing) spends its last steps clamped: the index
  map stays on the last block fetched, so nothing is fetched, and the body is
  predicated off.  Visible pairs are visited in ascending order with the same
  tiles and the same body whatever the band, so the outputs are bit for bit
  those of the walk over the whole range (tests/test_flash_attention.py hands
  ``_fwd_pallas`` / ``_bwd_pallas`` that walk and compares with array_equal).
- causality is exploited at block granularity inside the band too: ``_visible``
  (and the padding-mask / segment predicates, which the band knows nothing
  of) predicates fully-masked blocks off with ``pl.when`` (the MXU never sees
  them), matching the 2x FLOP saving the reference's kernel gets from causal
  masking.
- operand order: each kernel is one custom call whose FIRST operand is the
  4-D ``[b, heads, s, d]`` q (fwd returns ``(o, float32 lse)``, dq one array,
  dkv two), under the names and scopes ``flash_fwd`` / ``flash_dq`` /
  ``flash_dkv``: the benchmark's finder (benchmark/trace_reduce.py::flash_kind)
  knows them by that.  So the band's offsets are arithmetic in the index
  maps, not a scalar-prefetch table passed in ahead of q.
- the diagonal walk, a second walk with kernel bodies of its own (they share
  the mask helpers with the band walk and nothing of the walk): a causal
  self-attention call whose window is no wider than its square tile
  (``window <= bq == bkv``, ``q_offset == 0``, ``sq == skv``, no
  ``attention_mask``, no ``segment_ids``: ``_takes_diagonal``, from the
  call's own arguments at trace time) sees, from query block ``qi``, keys of
  blocks ``qi - 1`` and ``qi`` alone.  So the grid has no band dimension: one
  step a query block (fwd, dq: K and V handed in twice, index maps
  ``max(qi - 1, 0)`` and ``qi``) or a key block and group head (dkv: q, do,
  lse, delta handed in twice, ``ki`` and ``min(ki + 1, last)``), and the body
  walks the block in sub-tiles of ``r`` rows by ``r`` keys (``_sub_rows``;
  a static Python loop whose bounds are ``_kv_span`` / ``_q_span`` of the two
  blocks cut into sub-tiles, ``_sub_band``), skipping the sub-tiles the
  window hides and masking only those its edge or the diagonal crosses.
  Every visible key of a row is in hand at once, so the forward takes a
  plain softmax: one maximum and one sum a row, no rescaling, no scratch;
  dq sums in values and stores once; dkv keeps the band walk's scratch for
  the sum over the group.  The heads of a GQA group are adjacent in the grid,
  so K and V are fetched once a group.  Same operand dtypes as the band walk
  (bf16 into the score and p @ v matmuls, float32 accumulation and ``ds``),
  another order of summation: close to the band walk, not equal.  A long
  walk needs the online softmax and its scratch, and a walk that ends inside
  two blocks is slowed by exactly that (one v5e, window 512 x 72 heads x seq
  8192: 5.56 ms a forward call the band's way, 1.87 this way), hence two
  walks and not one adapted.  ``flash_band`` of ``run_summary.json`` says
  which a call took (``walk``) and, for this one, ``sub_tiles``
  ``[computed, of]`` those the band of a query block holds.
- GQA: the kv BlockSpec index-maps query-head ``h`` -> kv-head
  ``h // (nh // nkv)`` so K/V are never physically repeated (the reference
  replicates KV via ``kv_shared_group_size`` instead — unnecessary here).
- backward: two kernels (dq with the kv band innermost; dkv with the q band
  innermost, grid = (batch, kv_heads, kv_blocks, group, q_band)), both
  recomputing p = exp(s - lse) from the saved logsumexp, FlashAttention-2
  style.  dk/dv are produced per KV-head: the GQA q-head group is a sequential
  grid dim accumulated in fp32 VMEM scratch.

Layout contract matches ``core_attention``: q [b, sq, nh, d], k/v
[b, skv, nkv, d], output [b, sq, nh, d].
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_training_tpu.parallel import sharding as shd

logger = logging.getLogger(__name__)

LANES = 128  # TPU lane width; scratch minor dims and block sizes align to it
SUBLANES = 8  # minor dim for per-row stats (lse/delta): the smallest legal
# Mosaic block minor dim — 16x less HBM than a full 128-lane broadcast
DEFAULT_BLOCK_Q = 512
# the TPU compiler accepts kv 2048 at every shape tests/test_tpu_compile.py
# lowers (Llama widths, d 128, seq 4096/8192, fwd + dq + dkv); its speed
# against other tiles has not been measured on the current code.  Still a
# per-chip knob via fusions.flash_block_kv.
DEFAULT_BLOCK_KV = 2048
NEG_INF = -1e30
#: ``checkpoint_name``s of the plain call's forward outputs, o and lse
KEPT_NAMES = ("flash_o", "flash_lse")
#: how heads of 64 dims reach the kernels (``flash_band``'s ``feed``): blocks
#: whose last dim is the array's 64, nothing padded, no heads folded into rows.
#: One v5e, one layer at 32 / 8 heads x 64, two sequences of 8192, forward +
#: backward at tiles of 512 x 2048 (PERF.md section 6, PR 43): 41.14 ms as
#: fed; 42.01 with q, k and v zero-padded to 128 (twice their bytes and the
#: score FLOPs for nothing: the exponentials bound the kernels either way);
#: the 4 query heads of a key/value group folded into the query block's rows,
#: forward alone, 12.13 against 12.15: K and V fetched once a group buy nothing
HALF_LANE_FEED = "whole"


def _block_sizes(sq: int, skv: int, bq: Optional[int], bkv: Optional[int],
                 dtype=jnp.bfloat16, d: int = LANES):
    # 4-byte operands get half the kv block: at 2048 the dkv kernel of an
    # MHA layer (s 4096, d 128, float32) needs 17.08 MiB of the TPU's 16 MiB
    # scoped VMEM and the compiler refuses it
    default_q = DEFAULT_BLOCK_Q
    default_kv = DEFAULT_BLOCK_KV // max(jnp.dtype(dtype).itemsize // 2, 1)
    if d > LANES:
        # and so do score dims past one lane width: at 2048 the dkv kernel
        # of 32 heads x 192 (held in VMEM as 256) x seq 8192, bf16, needs
        # 16.73 MiB; 1024 is taken
        default_kv //= 2
    elif d < LANES:
        # heads of half a lane width: the kernels are bound by the scores'
        # exponentials, not by the 64-deep contraction, and a square tile of
        # 1024 x 1024 walks them fastest.  One v5e, one layer at 32 / 8 heads
        # x 64, two sequences of 8192, forward / forward + backward, ms
        # (PERF.md section 6, PR 43): 512 x 2048 12.15 / 41.14, 512 x 1024
        # 12.76 / 41.43, **1024 x 1024 10.72 / 39.28**, 256 x 2048 13.51 /
        # 44.92; 1024 x 2048 and 512 x 4096 need more VMEM than a kernel gets
        default_q, default_kv = 2 * default_q, default_kv // 2
    bq = bq or min(default_q, sq)
    bkv = bkv or min(default_kv, skv)
    while sq % bq:
        bq //= 2
    while skv % bkv:
        bkv //= 2
    return max(bq, 1), max(bkv, 1)


def _tileable(sq: int, skv: int, d: int, bq: int, bkv: int,
              d_v: Optional[int] = None) -> bool:
    """``d``: the dims q and k are scored over, ``d_v`` (default ``d``) those
    of v and the output.  Where they differ (latent attention: 192 and 128)
    the score dims may end on half a lane width, fed as they are: a block
    whose last dim is the array's.  Heads of half a lane width (``d == d_v ==
    64``) are fed the same way: every block of q, k, v, o and their cotangents
    64 wide as its array is, the contractions 64 deep, the accumulators 64
    lanes of a register's 128 (``HALF_LANE_FEED``)."""
    d_v = d if d_v is None else d_v
    dims = (d == d_v == LANES // 2
            or (d_v % LANES == 0 and d % (LANES if d == d_v else LANES // 2) == 0))
    return (
        sq % bq == 0
        and skv % bkv == 0
        and bq % LANES == 0
        and bkv % LANES == 0
        and dims
    )


def _visible(qi, ki, bq, bkv, causal: bool, window: Optional[int], q_offset: int):
    """Block-level visibility predicate (trace-time on program ids)."""
    q_lo = qi * bq + q_offset
    q_hi = q_lo + bq - 1
    kv_lo = ki * bkv
    kv_hi = kv_lo + bkv - 1
    vis = jnp.bool_(True)
    if causal:
        vis = jnp.logical_and(vis, kv_lo <= q_hi)
    if window is not None:
        vis = jnp.logical_and(vis, kv_hi > q_lo - window)
    return vis


def _inner_mask(bq, bkv, qi, ki, causal, window, q_offset):
    """Within-block additive mask [bq, bkv] (0 / NEG_INF)."""
    if not causal and window is None:
        return None
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = jnp.bool_(True)
    if causal:
        ok = jnp.logical_and(ok, kv_pos <= q_pos)
    if window is not None:
        ok = jnp.logical_and(ok, kv_pos > q_pos - window)
    return jnp.where(ok, 0.0, NEG_INF)


class _Band(NamedTuple):
    """The walk of a call's innermost grid dimension: which blocks a kernel
    visits for one outer block, taken from ``causal``, ``window`` and
    ``q_offset`` alone (a superset of what ``_visible`` admits; padding masks
    and segments predicate inside it).  ``kv`` / ``q`` are the dimension's
    extent for fwd + dq / dkv: the largest count of partners any outer block
    has.  The band of ``causal=False, window=None`` is the whole range walked
    from block 0, i.e. no band at all (what the tests compare against)."""

    causal: bool
    window: Optional[int]
    q_offset: int
    bq: int
    bkv: int
    num_q: int
    num_kv: int
    kv: int
    q: int


def _kv_span(band, qi, lo=max, hi=min):
    """First and last key block query block ``qi`` can see: ``_visible``'s two
    inequalities solved for ``ki``.  Plain integer arithmetic on non-negative
    operands, so it serves Python ints (``lo=max, hi=min``) and program ids in
    an index map or a kernel body (``jnp.maximum``, ``jnp.minimum``) alike.
    ``last < first``: the block sees nothing."""
    q_lo = qi * band.bq + band.q_offset
    first, last = 0, band.num_kv - 1
    if band.window is not None:  # kv_hi > q_lo - window
        first = lo(q_lo - band.window + 1, 0) // band.bkv
    if band.causal:  # kv_lo <= q_hi; a q_hi below 0 gives -1
        last = hi(lo(q_lo + band.bq - 1 + band.bkv, 0) // band.bkv - 1, last)
    return first, last


def _q_span(band, ki, lo=max, hi=min):
    """First and last query block that can see key block ``ki`` (dkv's walk)."""
    kv_lo = ki * band.bkv - band.q_offset
    first, last = 0, band.num_q - 1
    if band.causal:  # kv_lo <= q_hi
        first = lo(kv_lo, 0) // band.bq
    if band.window is not None:  # kv_hi > q_lo - window
        q_lo_max = kv_lo + band.bkv - 1 + band.window - 1
        last = hi(lo(q_lo_max + band.bq, 0) // band.bq - 1, last)
    return first, last


def _band(bq, bkv, num_q, num_kv, causal, window, q_offset) -> _Band:
    """The band of a call, at trace time: every argument is a Python int."""
    band = _Band(causal, window, q_offset, bq, bkv, num_q, num_kv, num_kv, num_q)

    def longest(span, outer):
        spans = (span(band, i) for i in range(outer))
        return max(1, max(last - first + 1 for first, last in spans))

    return band._replace(kv=longest(_kv_span, num_q), q=longest(_q_span, num_kv))


def _call_band(bq, bkv, num_q, num_kv, causal, window, q_offset,
               sub: Optional[_Band] = None, dims: Optional[tuple] = None) -> _Band:
    """``_band`` of a forward call, recorded for ``run_summary.json`` where a
    trace collects such facts: ``flash_band``, one entry per distinct shape.
    ``sub`` (``_sub_band``): the call takes the diagonal walk, and
    ``sub_tiles`` says how many ``[computed, of]`` the sub-tiles that the
    band of a query block holds.  ``dims``: ``(d_qk, d_v)``, said where they
    differ, with how the score dims are fed (``feed: whole``: q and k blocks
    as wide as their arrays, one contraction over all of ``d_qk``)."""
    band = _band(bq, bkv, num_q, num_kv, causal, window, q_offset)
    facts = shd.trace_facts()
    if facts is not None:
        shape = {"seq": num_q * bq, "kv_blocks": num_kv, "kv_band": band.kv,
                 "q_blocks": num_q, "q_band": band.q, "walk": "band"}
        if dims is not None and dims[0] != dims[1]:
            shape.update(d_qk=dims[0], d_v=dims[1], feed="whole")
        elif dims is not None and dims[0] % LANES:
            shape.update(d=dims[0], feed=HALF_LANE_FEED)
        if sub is not None:
            n = sub.num_q // 2
            spans = (_kv_span(sub, n + a) for a in range(n))
            shape.update(walk="diagonal", sub_tiles=[
                sum(last - first + 1 for first, last in spans), band.kv * n * n])
        if shape not in facts.setdefault("flash_band", []):
            facts["flash_band"].append(shape)
    return band


def _walk(span, band, outer, j):
    """Step ``j`` of outer block ``outer``'s walk, on program ids -> (absolute
    inner block index, whether the step is inside the block's span, the block
    to fetch).  Past the block's last partner (the first windows, the
    sequence's ends, a block that sees nothing at all) the fetch stays on the
    last block fetched, so the step moves no data, and the kernel body
    predicates it off."""
    first, last = span(band, outer, jnp.maximum, jnp.minimum)
    i = first + j
    return i, i <= last, jnp.maximum(jnp.minimum(i, last), 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, *refs,
    sm_scale, causal, window, q_offset, bq, bkv, band, masked, segmented,
):
    refs = list(refs)
    kvm_ref = refs.pop(0) if masked else None
    segq_ref = refs.pop(0) if segmented else None
    segk_ref = refs.pop(0) if segmented else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    j = pl.program_id(3)
    ki, in_band, _ = _walk(_kv_span, band, qi, j)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    vis = jnp.logical_and(
        in_band, _visible(qi, ki, bq, bkv, causal, window, q_offset))
    if kvm_ref is not None:
        # skip kv blocks that are entirely padding (long pad tails cost 0 MXU)
        vis = jnp.logical_and(vis, jnp.any(kvm_ref[0] > 0))
    if segq_ref is not None:
        # packed-chunk segments are contiguous non-decreasing runs: a kv
        # block strictly ahead of every query segment can't match anything
        vis = jnp.logical_and(vis, jnp.min(segk_ref[0]) <= jnp.max(segq_ref[0]))

    @pl.when(vis)
    def _compute():
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bkv, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        mask = _inner_mask(bq, bkv, qi, ki, causal, window, q_offset)
        if mask is not None:
            s = s + mask
        if kvm_ref is not None:
            # padded KEYS masked (the HF attention_mask contract) — [1, bkv]
            # broadcasts over query rows
            s = jnp.where(kvm_ref[0] > 0, s, NEG_INF)
        if segq_ref is not None:
            # block-diagonal packed-sequence mask: attend only within the
            # same segment ([bq, 1] vs [1, bkv] broadcast)
            s = jnp.where(
                segq_ref[0].reshape(-1, 1) == segk_ref[0].reshape(1, -1),
                s, NEG_INF,
            )
        m_prev = m_scr[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bkv]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == band.kv - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # a row with NO visible key anywhere keeps m ~= NEG_INF: its p values
        # were exp(s - m) over masked-only scores (garbage, since the finite
        # NEG_INF cancels) -> force output 0 and lse = NEG_INF.  Rows masked in
        # one block but visible in another self-correct via alpha rescaling.
        row_visible = m_scr[:, :1] > NEG_INF / 2
        o_ref[0, 0] = jnp.where(
            row_visible, acc_scr[:] / l_safe, 0.0
        ).astype(o_ref.dtype)
        lse = jnp.where(row_visible, m_scr[:, :1] + jnp.log(l_safe), NEG_INF)
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], SUBLANES))


def _fwd_pallas(q, k, v, kvm, seg, *, sm_scale, causal, window, q_offset, bq, bkv,
                interpret, band=None):
    """q [b, nh, sq, d]; k [b, nkv, skv, d]; v [b, nkv, skv, dv]; kvm None or
    [b, 1, skv] int32 (1 = real key); seg None or [b, 1, s] int32 segment ids
    (self-attention packed chunks) -> (o [b, nh, sq, dv], lse [b, nh, sq,
    SUBLANES]).  ``band``: the walk, ``_band`` of the call unless a test hands
    in another."""
    b, nh, sq, d = q.shape
    nkv, skv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = nh // nkv
    num_q, num_kv = sq // bq, skv // bkv
    if band is None:
        band = _call_band(bq, bkv, num_q, num_kv, causal, window, q_offset,
                          dims=(d, dv))

    def kv_at(qi, j):  # the key block step j of query block qi fetches
        return _walk(_kv_span, band, qi, j)[2]

    grid = (b, nh, num_q, band.kv)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bkv=bkv, band=band, masked=kvm is not None,
        segmented=seg is not None,
    )
    # q stays the first operand (no scalar-prefetch table in front of it): the
    # benchmark's finder knows the kernels by their operands' shapes
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, j: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bkv, d),
                     lambda bi, hi, qi, j: (bi, hi // group, kv_at(qi, j), 0)),
        pl.BlockSpec((1, 1, bkv, dv),
                     lambda bi, hi, qi, j: (bi, hi // group, kv_at(qi, j), 0)),
    ]
    in_arrays = [q, k, v]
    if kvm is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda bi, hi, qi, j: (bi, 0, kv_at(qi, j))))
        in_arrays.append(kvm)
    if seg is not None:
        # same [b, 1, s] array read twice: query rows and key cols
        in_specs.append(pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, j: (bi, 0, qi)))
        in_arrays.append(seg)
        in_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda bi, hi, qi, j: (bi, 0, kv_at(qi, j))))
        in_arrays.append(seg)
    # the scope and the kernel's own name (telemetry.spans.DEVICE_SCOPES): a
    # trace reduction finds the three kernels by them, not by HLO numbering
    with jax.named_scope("flash_fwd"):
        o, lse = pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, j: (bi, hi, qi, 0)),
                pl.BlockSpec((1, 1, bq, SUBLANES), lambda bi, hi, qi, j: (bi, hi, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, nh, sq, dv), q.dtype),
                jax.ShapeDtypeStruct((b, nh, sq, SUBLANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(*in_arrays)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
    sm_scale, causal, window, q_offset, bq, bkv, band, masked, segmented,
):
    refs = list(refs)
    kvm_ref = refs.pop(0) if masked else None
    segq_ref = refs.pop(0) if segmented else None
    segk_ref = refs.pop(0) if segmented else None
    dq_ref, acc_scr = refs
    qi = pl.program_id(2)
    j = pl.program_id(3)
    ki, in_band, _ = _walk(_kv_span, band, qi, j)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    vis = jnp.logical_and(
        in_band, _visible(qi, ki, bq, bkv, causal, window, q_offset))
    if kvm_ref is not None:
        vis = jnp.logical_and(vis, jnp.any(kvm_ref[0] > 0))
    if segq_ref is not None:
        vis = jnp.logical_and(vis, jnp.min(segk_ref[0]) <= jnp.max(segq_ref[0]))

    @pl.when(vis)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        mask = _inner_mask(bq, bkv, qi, ki, causal, window, q_offset)
        if mask is not None:
            s = s + mask
        if kvm_ref is not None:
            # re-apply the key padding mask — p must be 0 on padded keys or
            # dq leaks gradient through them
            s = jnp.where(kvm_ref[0] > 0, s, NEG_INF)
        if segq_ref is not None:
            s = jnp.where(
                segq_ref[0].reshape(-1, 1) == segk_ref[0].reshape(1, -1),
                s, NEG_INF,
            )
        # rows with no visible key anywhere carry lse = NEG_INF; exp(s - lse)
        # would be garbage there, so zero them (matches fwd's 0 output)
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [bq, bkv]
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        # keep ds in fp32 for the dq matmul — same accumulation precision as
        # the dk/dv path (a bf16 downcast here systematically biases dq)
        acc_scr[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == band.kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
    sm_scale, causal, window, q_offset, bq, bkv, band, group, masked, segmented,
):
    refs = list(refs)
    kvm_ref = refs.pop(0) if masked else None
    segq_ref = refs.pop(0) if segmented else None
    segk_ref = refs.pop(0) if segmented else None
    dk_ref, dv_ref, dk_scr, dv_scr = refs
    ki = pl.program_id(2)
    g = pl.program_id(3)
    j = pl.program_id(4)
    qi, in_band, _ = _walk(_q_span, band, ki, j)

    @pl.when(jnp.logical_and(g == 0, j == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    vis = jnp.logical_and(
        in_band, _visible(qi, ki, bq, bkv, causal, window, q_offset))
    if kvm_ref is not None:
        vis = jnp.logical_and(vis, jnp.any(kvm_ref[0] > 0))
    if segq_ref is not None:
        vis = jnp.logical_and(vis, jnp.min(segk_ref[0]) <= jnp.max(segq_ref[0]))

    @pl.when(vis)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        mask = _inner_mask(bq, bkv, qi, ki, causal, window, q_offset)
        if mask is not None:
            s = s + mask
        if kvm_ref is not None:
            s = jnp.where(kvm_ref[0] > 0, s, NEG_INF)
        if segq_ref is not None:
            s = jnp.where(
                segq_ref[0].reshape(-1, 1) == segk_ref[0].reshape(1, -1),
                s, NEG_INF,
            )
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [bq, bkv]
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale  # [bq, bkv]
        # dk += ds^T @ q
        dk_scr[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(g == group - 1, j == band.q - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _delta_rows(g, o, dlse):
    """The backward kernels' ``delta`` operand [b, nh, sq, SUBLANES]."""
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)  # [b, nh, sq]
    if dlse is not None:
        # lse exposed as a differentiable output (ring merge): d lse / d s = p,
        # so ds = p*(dp - delta + dlse) — fold dlse into the delta operand
        delta = delta - dlse
    return jnp.broadcast_to(delta[..., None], delta.shape + (SUBLANES,))


def _bwd_pallas(res, g, *, sm_scale, causal, window, q_offset, bq, bkv, interpret,
                dlse=None, band=None):
    # q [b, nh, sq, d]; k [b, nkv, skv, d]; v [b, nkv, skv, dv]; o, g as v
    q, k, v, kvm, seg, o, lse = res
    b, nh, sq, d = q.shape
    nkv, skv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = nh // nkv
    num_q, num_kv = sq // bq, skv // bkv
    if band is None:
        band = _band(bq, bkv, num_q, num_kv, causal, window, q_offset)

    def kv_at(qi, j):  # dq walks a query block's key blocks,
        return _walk(_kv_span, band, qi, j)[2]

    def q_at(ki, j):  # dkv a key block's query blocks
        return _walk(_q_span, band, ki, j)[2]

    delta = _delta_rows(g, o, dlse)

    common = dict(sm_scale=sm_scale, causal=causal, window=window, q_offset=q_offset,
                  bq=bq, bkv=bkv, band=band, masked=kvm is not None,
                  segmented=seg is not None)
    in_arrays = (q, k, v, g, lse, delta) + ((kvm,) if kvm is not None else ())
    if seg is not None:
        in_arrays = in_arrays + (seg, seg)

    def dq_q(width):  # dq: a [.., bq, width] block of query block qi
        return pl.BlockSpec((1, 1, bq, width), lambda bi, hi, qi, j: (bi, hi, qi, 0))

    def dq_kv(width):  # dq: the key block that step j of query block qi visits
        return pl.BlockSpec(
            (1, 1, bkv, width),
            lambda bi, hi, qi, j: (bi, hi // group, kv_at(qi, j), 0))

    dq_specs = [dq_q(d), dq_kv(d), dq_kv(dv), dq_q(dv), dq_q(SUBLANES),
                dq_q(SUBLANES)]
    if kvm is not None:
        dq_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda bi, hi, qi, j: (bi, 0, kv_at(qi, j))))
    if seg is not None:
        dq_specs.append(pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, j: (bi, 0, qi)))
        dq_specs.append(pl.BlockSpec(
            (1, 1, bkv), lambda bi, hi, qi, j: (bi, 0, kv_at(qi, j))))
    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common),
            name="flash_dq",
            grid=(b, nh, num_q, band.kv),
            in_specs=dq_specs,
            out_specs=dq_q(d),
            out_shape=jax.ShapeDtypeStruct((b, nh, sq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(*in_arrays)

    # dk/dv per KV-head: the q-head group is a sequential grid dim, accumulated
    # in the fp32 VMEM scratch — 1x HBM writes and no bf16 intermediate in the
    # GQA group sum.
    def dkv_q(width):  # dkv: the query block that step j of key block ki visits
        return pl.BlockSpec(
            (1, 1, bq, width),
            lambda bi, kh, ki, g, j: (bi, kh * group + g, q_at(ki, j), 0))

    def dkv_kv(width):  # dkv: key block ki
        return pl.BlockSpec((1, 1, bkv, width), lambda bi, kh, ki, g, j: (bi, kh, ki, 0))

    dkv_specs = [dkv_q(d), dkv_kv(d), dkv_kv(dv), dkv_q(dv), dkv_q(SUBLANES),
                 dkv_q(SUBLANES)]
    if kvm is not None:
        dkv_specs.append(pl.BlockSpec((1, 1, bkv), lambda bi, kh, ki, g, j: (bi, 0, ki)))
    if seg is not None:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, bq), lambda bi, kh, ki, g, j: (bi, 0, q_at(ki, j))))
        dkv_specs.append(pl.BlockSpec((1, 1, bkv), lambda bi, kh, ki, g, j: (bi, 0, ki)))
    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, group=group, **common),
            name="flash_dkv",
            grid=(b, nkv, num_kv, group, band.q),
            in_specs=dkv_specs,
            out_specs=[dkv_kv(d), dkv_kv(dv)],
            out_shape=[
                jax.ShapeDtypeStruct((b, nkv, skv, d), k.dtype),
                jax.ShapeDtypeStruct((b, nkv, skv, dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), jnp.float32),
                pltpu.VMEM((bkv, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(*in_arrays)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# the diagonal walk: a window no wider than the (square) tile
# ---------------------------------------------------------------------------


def _takes_diagonal(q, k, kvm, seg, causal, window, q_offset, bq, bkv) -> bool:
    """Whether a call goes the diagonal walk: all from its own arguments."""
    return bool(
        causal and window is not None and window <= bq == bkv and q_offset == 0
        and q.shape[2] == k.shape[2] and kvm is None and seg is None)


def _sub_rows(bq: int, window: int) -> int:
    """Rows (and keys) of a sub-tile of the diagonal walk.  A query block
    computes ``bq x (window + r)`` scores for the ``bq x window`` it shows, so
    a smaller ``r`` skips more, and feeds the MXU shorter matmuls.  One v5e, one
    layer at 72 / 8 heads x 128, seq 8192, tiles 512, forward twice + dq +
    dkv, ms (PERF.md section 6, PR 38): window 512: r 128 9.98, **256 9.04**,
    512 10.84 (the band walk 18.62); window 256: 8.29, **7.44**; window 128:
    **7.20**, 7.45."""
    r = 2 * LANES
    return LANES if window <= LANES or bq % r else r


def _sub_band(bq: int, window: int) -> _Band:
    """Two neighbouring blocks as one sequence cut into sub-tiles of ``r`` rows
    by ``r`` keys (fwd, dq: a query block, second, with the key block before
    it; dkv: a key block, first, with the query block after it).  Under
    ``window <= bq`` nothing else is visible, so ``_kv_span`` / ``_q_span`` of
    this band, on Python ints, are the sub-tiles a row or key sub-block
    meets, counted from the first block's start."""
    r = _sub_rows(bq, window)
    n = 2 * (bq // r)
    return _Band(True, window, 0, r, r, n, n, n, n)


def _sub_masks(sub: _Band, pairs) -> dict:
    """``_inner_mask`` of those of ``pairs`` (row sub-block, key sub-tile) that
    the window's edge or the diagonal crosses, keyed by the one thing such a
    mask depends on: how many sub-tiles the rows lie after the keys.  A pair
    with no entry is wholly visible."""
    r = sub.bq
    crossed = {a - j for a, j in pairs
               if (a - j) * r - (r - 1) < 0 or (a - j) * r + r - 1 >= sub.window}
    return {lag: _inner_mask(r, r, lag, 0, True, sub.window, 0) for lag in sorted(crossed)}


def _sub_tile(first_ref, second_ref, sub: _Band, i, width=None):
    """Sub-block ``i`` (counted over both blocks) of a pair of block refs."""
    r, n = sub.bq, sub.num_q // 2
    ref = first_ref if i < n else second_ref
    x = ref[0, 0, pl.ds((i % n) * r, r), :]
    return x if width is None else x[:, :width]


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _at_edge_or_not(at_edge, walk):
    """``walk(True)`` for the block at the sequence's edge, which lacks its
    neighbour (the index map hands it a stand-in), else ``walk(False)``."""
    pl.when(at_edge)(lambda: walk(True))
    pl.when(jnp.logical_not(at_edge))(lambda: walk(False))


_QK = ((1,), (1,))  # [rows, d] x [keys, d] -> [rows, keys]
_PV = ((1,), (0,))  # [rows, keys] x [keys, d] -> [rows, d]
_TN = ((0,), (0,))  # [rows, keys] x [rows, d] -> [keys, d]


def _row_spans(sub: _Band):
    """fwd, dq: the key sub-tiles each row sub-block of the query block (the
    second of the two blocks) meets, and the masks of those that need one."""
    n = sub.num_q // 2
    spans = [_kv_span(sub, n + a) for a in range(n)]
    return spans, _sub_masks(sub, [(n + a, j) for a, (first, last) in enumerate(spans)
                                   for j in range(first, last + 1)])


def _diag_fwd_kernel(q_ref, kp_ref, kc_ref, vp_ref, vc_ref, o_ref, lse_ref, *,
                     sm_scale, sub):
    r, n = sub.bq, sub.num_q // 2
    spans, masks = _row_spans(sub)

    def walk(first_block):
        for a, (first, last) in enumerate(spans):
            rows = pl.ds(a * r, r)
            q = q_ref[0, 0, rows, :]
            tiles = range(max(first, n) if first_block else first, last + 1)
            scores = []
            for j in tiles:
                s = _dot(q, _sub_tile(kp_ref, kc_ref, sub, j), _QK) * sm_scale
                mask = masks.get(n + a - j)
                scores.append(s if mask is None else s + mask)
            # every visible key of these rows is in hand (each row sees itself
            # at least): a plain softmax, one maximum and one sum a row
            m = jnp.max(functools.reduce(jnp.maximum, scores), axis=-1, keepdims=True)
            probs = [jnp.exp(s - m) for s in scores]
            l = jnp.sum(functools.reduce(jnp.add, probs), axis=-1, keepdims=True)
            acc = functools.reduce(jnp.add, (
                _dot(p.astype(vc_ref.dtype), _sub_tile(vp_ref, vc_ref, sub, j), _PV)
                for p, j in zip(probs, tiles)))
            o_ref[0, 0, rows, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
            lse_ref[0, 0, rows, :] = jnp.broadcast_to(m + jnp.log(l), (r, SUBLANES))

    _at_edge_or_not(pl.program_id(2) == 0, walk)  # no block before the first


def _diag_dq_kernel(q_ref, kp_ref, kc_ref, vp_ref, vc_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, *, sm_scale, sub):
    r, n = sub.bq, sub.num_q // 2
    spans, masks = _row_spans(sub)

    def walk(first_block):
        for a, (first, last) in enumerate(spans):
            rows = pl.ds(a * r, r)
            q = q_ref[0, 0, rows, :]
            do = do_ref[0, 0, rows, :]
            lse = lse_ref[0, 0, rows, :][:, :1]
            delta = delta_ref[0, 0, rows, :][:, :1]
            dq = None
            for j in range(max(first, n) if first_block else first, last + 1):
                k = _sub_tile(kp_ref, kc_ref, sub, j)
                s = _dot(q, k, _QK) * sm_scale
                mask = masks.get(n + a - j)
                p = jnp.exp((s if mask is None else s + mask) - lse)
                # do and v are what the band walk casts to float32 first: the
                # same products, summed in float32 either way
                dp = _dot(do, _sub_tile(vp_ref, vc_ref, sub, j), _QK)
                ds = p * (dp - delta) * sm_scale
                # float32 beside ds and p, as in the band walk's kernels
                part = _dot(ds, k.astype(jnp.float32), _PV)  # jaxlint: disable=JL106
                dq = part if dq is None else dq + part
            dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)

    _at_edge_or_not(pl.program_id(2) == 0, walk)


def _diag_dkv_kernel(qc_ref, qn_ref, k_ref, v_ref, doc_ref, don_ref, lsec_ref, lsen_ref,
                     deltac_ref, deltan_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                     sm_scale, sub, group, num_kv):
    r, n = sub.bq, sub.num_q // 2
    spans = [_q_span(sub, j) for j in range(n)]
    masks = _sub_masks(sub, [(a, j) for j, (first, last) in enumerate(spans)
                             for a in range(first, last + 1)])
    g = pl.program_id(3)

    @pl.when(g == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def walk(last_block):
        for j, (first, last) in enumerate(spans):
            keys = pl.ds(j * r, r)
            k = k_ref[0, 0, keys, :]
            v = v_ref[0, 0, keys, :]
            dk = dv = None
            for a in range(first, min(last, n - 1) + 1 if last_block else last + 1):
                q = _sub_tile(qc_ref, qn_ref, sub, a)
                do = _sub_tile(doc_ref, don_ref, sub, a)
                lse = _sub_tile(lsec_ref, lsen_ref, sub, a, 1)
                delta = _sub_tile(deltac_ref, deltan_ref, sub, a, 1)
                s = _dot(q, k, _QK) * sm_scale
                mask = masks.get(a - j)
                p = jnp.exp((s if mask is None else s + mask) - lse)
                dv_part = _dot(p, do.astype(jnp.float32), _TN)  # jaxlint: disable=JL106
                dp = _dot(do, v, _QK)
                ds = p * (dp - delta) * sm_scale
                dk_part = _dot(ds, q.astype(jnp.float32), _TN)  # jaxlint: disable=JL106
                dk = dk_part if dk is None else dk + dk_part
                dv = dv_part if dv is None else dv + dv_part
            dk_scr[keys, :] += dk
            dv_scr[keys, :] += dv

    # no query block after the last key block
    _at_edge_or_not(pl.program_id(2) == num_kv - 1, walk)

    @pl.when(g == group - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _diag_specs(b, nh, nkv, s, d, bq):
    """Grid and block specs the diagonal walk's forward and dq share: the
    heads of a GQA group are adjacent in the grid, so the key blocks' indices
    stay put over them and are fetched once a group."""
    group = nh // nkv

    def q_spec(width):
        return pl.BlockSpec((1, 1, bq, width),
                            lambda bi, kh, qi, g: (bi, kh * group + g, qi, 0))

    def kv_spec(back):  # key block qi - back; block 0 stands in before the first
        return pl.BlockSpec((1, 1, bq, d),
                            lambda bi, kh, qi, g: (bi, kh, jnp.maximum(qi - back, 0), 0))

    params = pltpu.CompilerParams(dimension_semantics=("parallel",) * 4)
    return (b, nkv, s // bq, group), q_spec, kv_spec, params


def _diag_fwd(q, k, v, *, sm_scale, window, bq, interpret):
    """The diagonal walk's forward: as ``_fwd_pallas``, one grid step a query
    block, K and V handed in twice (the block before, the block itself)."""
    b, nh, s, d = q.shape
    nkv = k.shape[1]
    sub = _sub_band(bq, window)
    _call_band(bq, bq, s // bq, s // bq, True, window, 0, sub=sub)
    grid, q_spec, kv_spec, params = _diag_specs(b, nh, nkv, s, d, bq)
    with jax.named_scope("flash_fwd"):
        o, lse = pl.pallas_call(
            functools.partial(_diag_fwd_kernel, sm_scale=sm_scale, sub=sub),
            name="flash_fwd",
            grid=grid,
            in_specs=[q_spec(d), kv_spec(1), kv_spec(0), kv_spec(1), kv_spec(0)],
            out_specs=[q_spec(d), q_spec(SUBLANES)],
            out_shape=[
                jax.ShapeDtypeStruct((b, nh, s, d), q.dtype),
                jax.ShapeDtypeStruct((b, nh, s, SUBLANES), jnp.float32),
            ],
            compiler_params=params,
            interpret=interpret,
        )(q, k, k, v, v)
    return o, lse


def _diag_bwd(res, g, *, sm_scale, window, bq, interpret, dlse=None):
    """The diagonal walk's backward: as ``_bwd_pallas``.  dq takes the
    forward's grid and operands; dkv takes one grid step a key block and
    group head, with q, do, lse and delta handed in twice (the query block of
    the key block's own index, the one after)."""
    q, k, v, _, _, o, lse = res
    b, nh, s, d = q.shape
    nkv = k.shape[1]
    group, num_kv = nh // nkv, s // bq
    sub = _sub_band(bq, window)
    delta = _delta_rows(g, o, dlse)
    grid, q_spec, kv_spec, params = _diag_specs(b, nh, nkv, s, d, bq)
    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_diag_dq_kernel, sm_scale=sm_scale, sub=sub),
            name="flash_dq",
            grid=grid,
            in_specs=[q_spec(d), kv_spec(1), kv_spec(0), kv_spec(1), kv_spec(0),
                      q_spec(d), q_spec(SUBLANES), q_spec(SUBLANES)],
            out_specs=q_spec(d),
            out_shape=jax.ShapeDtypeStruct((b, nh, s, d), q.dtype),
            compiler_params=params,
            interpret=interpret,
        )(q, k, k, v, v, g, lse, delta)

    def rows(width, ahead):  # query block ki + ahead; the last stands in past it
        return pl.BlockSpec(
            (1, 1, bq, width), lambda bi, kh, ki, gi: (
                bi, kh * group + gi, jnp.minimum(ki + ahead, num_kv - 1), 0))

    keys = pl.BlockSpec((1, 1, bq, d), lambda bi, kh, ki, gi: (bi, kh, ki, 0))
    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_diag_dkv_kernel, sm_scale=sm_scale, sub=sub,
                              group=group, num_kv=num_kv),
            name="flash_dkv",
            grid=(b, nkv, num_kv, group),
            in_specs=[rows(d, 0), rows(d, 1), keys, keys, rows(d, 0), rows(d, 1),
                      rows(SUBLANES, 0), rows(SUBLANES, 1),
                      rows(SUBLANES, 0), rows(SUBLANES, 1)],
            out_specs=[keys, keys],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(q, q, k, v, g, g, lse, lse, delta, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom_vjp over the [b, s, h, d] layout)
# ---------------------------------------------------------------------------


def _one_head_dim(q, v) -> bool:
    """The diagonal walk's kernels know one head dim, of whole lane widths; a
    call that scores over other dims than it weighs (latent attention), or
    whose heads are half a lane wide, keeps the band walk."""
    return q.shape[-1] == v.shape[-1] and q.shape[-1] % LANES == 0


def _forward(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret):
    """``(o, lse)`` by the walk the call's own arguments choose."""
    sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _one_head_dim(q, v) and _takes_diagonal(
            q, k, kvm, seg, causal, window, q_offset, bq, bkv):
        return _diag_fwd(q, k, v, sm_scale=sm_scale, window=window, bq=bq,
                         interpret=interpret)
    return _fwd_pallas(
        q, k, v, kvm, seg, sm_scale=sm_scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bkv=bkv, interpret=interpret,
    )


def _mask_cotangent(kvm):
    """Zero cotangent for the (non-differentiable) int32 key mask: integer
    primals carry ``float0`` tangents in JAX."""
    if kvm is None:
        return None
    import numpy as np

    return np.zeros(kvm.shape, dtype=jax.dtypes.float0)


def _backward(causal, window, q_offset, bq, bkv, interpret, res, g, dlse=None):
    """``(dq, dk, dv)`` and the row operands' cotangents, by the forward's walk."""
    q, k, v, kvm, seg = res[:5]
    sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _one_head_dim(q, v) and _takes_diagonal(
            q, k, kvm, seg, causal, window, q_offset, bq, bkv):
        grads = _diag_bwd(res, g, sm_scale=sm_scale, window=window, bq=bq,
                          interpret=interpret, dlse=dlse)
    else:
        grads = _bwd_pallas(
            res, g, sm_scale=sm_scale, causal=causal, window=window,
            q_offset=q_offset, bq=bq, bkv=bkv, interpret=interpret, dlse=dlse,
        )
    return (*grads, _mask_cotangent(kvm), _mask_cotangent(seg))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret, keep):
    return _forward(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret)[0]


def _flash_fwd(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret, keep):
    """``keep``: name the kernel's two outputs (``KEPT_NAMES``) for a
    ``jax.checkpoint`` policy that keeps them, so that a rematerialized layer
    rebuilds q, k and v and does not call the forward kernel again
    (models/llama.py::_remat_policy, ``full``).  The row statistics then cross
    as ``[b, heads, s]``: as the kernel writes them, ``[..., SUBLANES]``
    float32, HBM pads the minor dim to a lane width and holds 16 times their
    bytes.  Without ``keep`` the rule is what it was, to the lowered text."""
    o, lse = _forward(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret)
    if keep:
        o = checkpoint_name(o, KEPT_NAMES[0])
        lse = checkpoint_name(lse[..., 0], KEPT_NAMES[1])
    return o, (q, k, v, kvm, seg, o, lse)


def _flash_bwd(causal, window, q_offset, bq, bkv, interpret, keep, res, g):
    if keep:  # the SUBLANES columns the kernel wrote were all this one
        lse = jnp.broadcast_to(res[6][..., None], res[6].shape + (SUBLANES,))
        res = (*res[:6], lse)
    return _backward(causal, window, q_offset, bq, bkv, interpret, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# -- lse-exposing variant (the ring-attention building block) ----------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret):
    """Like ``_flash`` but returns ``(o, lse)`` with lse differentiable.

    ``lse [b, nh, sq]`` is the per-row logsumexp of the (scaled, masked)
    scores; rows with no visible key carry ``NEG_INF`` and o = 0.  Exposing it
    lets callers merge partial attention over KV chunks (context-parallel ring)
    with exact autodiff: the merge is plain JAX, and this op's vjp folds the
    lse cotangent into the kernel's delta operand.

    Its outputs carry no ``checkpoint_name`` (``_flash_fwd`` has them): a ring
    of n steps would keep n partial outputs a layer, and no measured cell runs
    one, so a layer rematerialized under ``full`` reruns this forward kernel.
    """
    o, lse = _forward(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret)
    return o, lse[..., 0]


def _flash_lse_fwd(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret):
    o, lse = _forward(q, k, v, kvm, seg, causal, window, q_offset, bq, bkv, interpret)
    return (o, lse[..., 0]), (q, k, v, kvm, seg, o, lse)


def _flash_lse_bwd(causal, window, q_offset, bq, bkv, interpret, res, g):
    do, dlse = g
    return _backward(causal, window, q_offset, bq, bkv, interpret, res, do, dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


@functools.lru_cache(maxsize=None)
def _warn_core_route(shapes: str) -> None:
    logger.warning(
        "flash_attention asked for shapes that do not tile the Pallas kernel "
        "(%s): running core_attention instead — allowed off the TPU only; on "
        "a TPU this raises", shapes)


def flash_tileable(sq: int, skv: int, d: int, nh: int, nkv: int,
                   block_q: Optional[int] = None,
                   block_kv: Optional[int] = None,
                   d_v: Optional[int] = None) -> bool:
    """True when these shapes can run the Pallas kernels (no fallback)."""
    bq, bkv = _block_sizes(sq, skv, block_q, block_kv, d=d)
    return _tileable(sq, skv, d, bq, bkv, d_v) and nh % nkv == 0


def _prep_rows(x, b, s, name):
    """Normalize a per-token ``[b, s]`` operand (``attention_mask``, 1 = real
    key; ``segment_ids``) to the kernels' int32 ``[b, 1, s]`` layout, or None.
    The unit middle dim is what lets a ``(1, 1, block)`` BlockSpec tile it for
    b > 1: Mosaic wants a block's second-to-last dim to be a multiple of 8 or
    the whole dim."""
    if x is None:
        return None
    if x.shape != (b, s):
        raise ValueError(
            f"{name} must be [batch, seq] = ({b}, {s}); got {x.shape}"
        )
    return x.astype(jnp.int32)[:, None, :]


def flash_attention_with_lse(
    q: jax.Array,  # [b, sq, nh, d]
    k: jax.Array,  # [b, skv, nkv, d]
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    q_offset: int = 0,
    attention_mask: Optional[jax.Array] = None,  # [b, skv] 1 = real key
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """(o [b, sq, nh, d], lse [b, nh, sq]) — the ring building block.

    No core fallback: callers must check ``flash_tileable`` first (the ring
    body needs lse, which core attention does not produce).
    """
    b, sq, nh, d = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    # NOTE: unlike ``flash_attention``, sliding_window is honored even when
    # causal=False — the ring's fully-visible past chunks need exactly that
    # (window mask at a static relative offset, no causal mask)
    bq, bkv = _block_sizes(sq, skv, block_q, block_kv, q.dtype, d)
    if not _tileable(sq, skv, d, bq, bkv, v.shape[-1]) or nh % nkv != 0:
        raise ValueError(
            f"flash_attention_with_lse: shapes not tileable "
            f"(sq={sq}, skv={skv}, d={d}, d_v={v.shape[-1]}, nh={nh}, nkv={nkv})"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    kvm = _prep_rows(attention_mask, b, skv, "attention_mask")
    o, lse = _flash_lse(qt, kt, vt, kvm, None, causal, sliding_window, q_offset,
                        bq, bkv, interpret)
    return jnp.swapaxes(o, 1, 2), lse


def flash_attention(
    q: jax.Array,  # [b, sq, nh, d]
    k: jax.Array,  # [b, skv, nkv, d]
    v: jax.Array,  # [b, skv, nkv, d_v]: d, or its own (latent attention)
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    q_offset: int = 0,
    attention_mask: Optional[jax.Array] = None,  # [b, skv] 1 = real key
    segment_ids: Optional[jax.Array] = None,  # [b, s] packed-chunk segments
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
    keep_outputs: bool = False,
) -> jax.Array:
    """Flash attention in the model's [b, s, h, d] layout.

    ``attention_mask`` masks padded KEYS (the HF contract, reference
    ``llama_model.py:94-101``) inside the kernel — padded SFT/DPO batches stay
    on the flash path instead of falling back to the O(s^2) core attention.
    ``segment_ids`` makes attention block-diagonal over packed-chunk segments
    (tokens attend only within their own record) — a correctness upgrade over
    the reference's ConcatDataset, whose packed records causally attend
    ACROSS record boundaries.
    A causal call under a window no wider than its (square) tile, without
    either of them, takes the diagonal walk (module docstring); with
    ``attention_mask`` or ``segment_ids`` (padded or packed SFT batches) the
    same window keeps the band walk: correct, not faster.
    Shapes that do not tile the kernel raise on a TPU.  Off the TPU (the CPU
    test mesh, where toy models have head dims far below a lane) they run
    ``core_attention`` with a warning per shape.
    ``interpret`` defaults to True off-TPU so tests run on CPU.
    ``keep_outputs``: the caller's layer is rematerialized under a policy that
    keeps ``KEPT_NAMES`` (``_flash_fwd``); the values are the same either way.
    """
    b, sq, nh, d = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    if not causal:
        sliding_window = None  # window is causal-only, matching core_attention
    bq, bkv = _block_sizes(sq, skv, block_q, block_kv, q.dtype, d)
    d_v = v.shape[-1]
    if not _tileable(sq, skv, d, bq, bkv, d_v) or nh % nkv != 0:
        shapes = f"sq={sq}, skv={skv}, d={d}, nh={nh}, nkv={nkv}" + (
            f", d_v={d_v}" if d_v != d else "")
        # a host query, not a traced value
        if jax.default_backend() == "tpu":  # jaxlint: disable=JL102
            raise ValueError(
                f"flash_attention: shapes do not tile the Pallas kernel "
                f"({shapes}: seq blocks and head_dim must be multiples of "
                f"{LANES}, or head_dim {LANES // 2}; score dims that differ from "
                f"the value dims multiples of {LANES // 2}); set "
                f"fusions.flash_attention: false for this model"
            )
        _warn_core_route(shapes)
        from neuronx_distributed_training_tpu.ops.attention import (
            core_attention,
            padding_mask_bias,
            segment_mask_bias,
        )

        bias = None
        if attention_mask is not None:
            bias = padding_mask_bias(attention_mask)
        if segment_ids is not None:
            sb = segment_mask_bias(segment_ids)
            bias = sb if bias is None else bias + sb
        return core_attention(
            q, k, v, causal=causal, q_offset=q_offset, sliding_window=sliding_window,
            bias=bias,
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qt = jnp.swapaxes(q, 1, 2)  # [b, nh, sq, d]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    kvm = _prep_rows(attention_mask, b, skv, "attention_mask")
    if segment_ids is not None and sq != skv:
        raise ValueError(
            "segment_ids need self-attention (sq == skv); got "
            f"sq={sq}, skv={skv}"
        )
    seg = _prep_rows(segment_ids, b, sq, "segment_ids")
    o = _flash(qt, kt, vt, kvm, seg, causal, sliding_window, q_offset, bq, bkv,
               interpret, keep_outputs)
    return jnp.swapaxes(o, 1, 2)
