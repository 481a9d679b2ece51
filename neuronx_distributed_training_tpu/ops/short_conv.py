"""Depthwise causal convolutions of a few taps: the middle of a gated short
convolution (LFM2's ``conv`` layers: two input-dependent gates around the
taps; most of this file), and the plain one with a bias and ``silu`` that a
Mamba-2 mixer runs over its ``x``, ``B`` and ``C`` channels (``causal_conv``,
at the end: its own pair of kernels on the same tiles, halo and ``keep``).

A layer's operator is ``in_proj`` ``[hidden, 3 x hidden]``, this middle and
``out_proj`` ``[hidden, hidden]`` (models/lfm2.py); the two matmuls are plain.
Per token ``t``, with ``[B ; C ; z]`` the projection's thirds, in that order,
and ``w [K, channels]`` one filter of ``K`` taps a channel (no bias):

    g_t = B_t * z_t
    c_t = sum_{j < K} w[j] * g_{t - (K - 1) + j}      (g zero before the start)
    y_t = C_t * c_t

No activation function anywhere in it.  The source holds the filter as
``conv.weight [channels, 1, K]``; here the taps lead, so that a tap is one
row over the lanes.  ``attention_mask`` (left padding: 1 = a real token) zeroes
``g`` at padded positions, so a sequence's first real tokens see zeros before
them, as an unpadded sequence's do.  ``segment_ids`` (packed documents) cut
the taps at a document's start: ``g_{t-d}`` counts only where token ``t - d``
lies in token ``t``'s document.  Both, and the sequence's start, reach the
kernels as ONE operand, ``keep [b, s, 8]``: ``keep[t, d]`` is 1 where
``g_{t-d}`` counts for token ``t`` (``_keep``, a few integer compares).

**How it is computed** is one way, whatever the call: two Pallas kernels, one
forward and one backward, over tiles of ``[rows, 3 x channels]`` with a halo of
8 rows (the block before for the forward's ``g_{t-d}``; the block after for
the backward's ``dc_{t+d}``).  The chain is memory-bound and the kernels move
what it must move and nothing else: forward the projection's three thirds read
and the result written (``4 x channels`` a token), backward the thirds and the
result's cotangent read and the thirds' cotangents written (``7 x channels``);
products and sums in float32 registers, rounded once where a result leaves.
Left to XLA's fusion the same chain materialises ``g`` and ``dc`` in float32
and, backward, moves about six times its least (PERF.md section 6, PR 43, has
both measured on one v5e at the benchmark's shape, beside the grouped
``lax.conv_general_dilated``).  The backward recomputes ``g`` and ``c`` from
the projection: nothing but the operands is kept for it.

The sequence dependency is a halo of ``K - 1`` tokens: a split of the sequence
over chips (context parallelism, sequence parallelism) or over time (cached
decode: the last ``K - 1`` inputs are the layer's state) has to carry it, and
this function does not: the family refuses those (models/lfm2.py).  On a mesh
the kernels are called per shard of the batch (as the flash kernels are:
``ops/attention.py::_flash_on_mesh``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import DATA_AXES

LANES = 128
SUBLANES = 8  # rows of a halo block, and of the taps' and their gradient's operand
#: a halo is one block of 8 rows, the taps one operand of 8
MAX_TAPS = SUBLANES
#: ``run_summary.json``'s ``short_conv.way``: how the middle is computed
WAY = "pallas"
_VMEM_LIMIT = 96 * 2**20   # a tile's float32 temporaries pass the default 16 MiB


def _f32(x):
    """Float32 inside the chain, as a norm's internals: the products and the
    taps' sum of bf16 operands, rounded once where a result leaves."""
    return x.astype(jnp.float32)  # jaxlint: disable=JL106


def bytes_per_token(channels: int, itemsize: int = 2) -> int:
    """The least the forward middle moves a token: the projection's three
    thirds read, the result written."""
    return 4 * channels * itemsize


def _tile_rows(s: int, c: int) -> int:
    """Rows of a tile: 256 at 2048 channels (a tile's operands and float32
    temporaries are a few MiB each), fewer for wider layers, a multiple of 8."""
    rows = max(SUBLANES, min(256, 256 * 2048 // max(c, 1)) // SUBLANES * SUBLANES)
    return min(rows, -(-s // SUBLANES) * SUBLANES)


def _keep(b: int, s: int, k: int, attention_mask, segment_ids) -> jax.Array:
    """``[b, s, 8]`` float32: column ``d < k`` is 1 where ``g_{t-d}`` counts for
    token ``t``: ``t - d`` lies in the sequence, is a real token and lies in
    ``t``'s document."""
    def back(rows, d):   # rows[t - d], zeros before the start
        return jnp.pad(rows, ((0, 0), (d, 0)))[:, :s]

    cols = []
    for d in range(k):
        ok = jnp.broadcast_to(jnp.arange(s)[None, :] >= d, (b, s))
        if attention_mask is not None:
            ok = ok & back(attention_mask.astype(bool), d)
        if d and segment_ids is not None:
            ok = ok & (segment_ids == back(segment_ids, d))
        cols.append(ok)
    return jnp.pad(_f32(jnp.stack(cols, axis=-1)), ((0, 0), (0, 0), (0, SUBLANES - k)))


def _rows_down(prev8, cur, shift: int):
    """``cur`` moved down by ``shift`` rows, its first rows the last of
    ``prev8`` (the 8 rows before it)."""
    if shift == 0:
        return cur
    rolled = pltpu.roll(cur, shift, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, prev8.shape, 0)
    head = jnp.where(row < shift, pltpu.roll(prev8, shift, 0), rolled[:SUBLANES])
    if cur.shape[0] == SUBLANES:   # a halo block moved down: nothing after the head
        return head
    return jnp.concatenate([head, rolled[SUBLANES:]], axis=0)


def _rows_up(cur, next8, shift: int):
    """``cur`` moved up by ``shift`` rows, its last rows the first of
    ``next8`` (the 8 rows after it)."""
    if shift == 0:
        return cur
    n = cur.shape[0]
    rolled = pltpu.roll(cur, n - shift, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, next8.shape, 0)
    tail = jnp.where(row >= SUBLANES - shift, pltpu.roll(next8, SUBLANES - shift, 0),
                     rolled[n - SUBLANES:])
    return jnp.concatenate([rolled[:n - SUBLANES], tail], axis=0)


def _gate_products(x_ref, xp_ref, c: int):
    """``(x, g, g of the 8 rows before)`` in float32."""
    x, xp = _f32(x_ref[0]), _f32(xp_ref[0])
    return x, x[:, :c] * x[:, 2 * c:], xp[:, :c] * xp[:, 2 * c:]


def _conv_sum(x, xp, keep, w, bias, k):
    """``bias + sum_d w[k - 1 - d] * keep[:, d] * x_{t-d}`` of a tile (``xp``
    the 8 rows before it; ``bias`` a row or None), float32."""
    acc = bias
    for d in range(k):   # tap k - 1 - d weighs x_{t-d}
        term = w[k - 1 - d:k - d] * (keep[:, d:d + 1] * _rows_down(xp, x, d))
        acc = term if acc is None else acc + term
    return acc


def _taps_back(x, xp, keep, keepn, w, dacc, daccn, k, with_sum: bool):
    """``_conv_sum``'s backward pass over a tile: ``dacc`` the sum's cotangent
    (``daccn``: of the 8 rows after, which read this tile's last rows) ->
    ``(the sum itself where with_sum, x's cotangent, the taps' gradient rows,
    tap 0 last)``."""
    conv = dx = None
    rows = []
    for d in range(k):
        back = _rows_down(xp, x, d)                       # x_{t-d}
        kept = keep[:, d:d + 1] * dacc                    # what x_{t-d} receives from t
        tap = w[k - 1 - d:k - d]
        if with_sum:
            term = tap * (keep[:, d:d + 1] * back)
            conv = term if conv is None else conv + term
        ahead = tap * _rows_up(kept, keepn[:, d:d + 1] * daccn, d)   # ... read at t - d
        dx = ahead if dx is None else dx + ahead
        rows.append(jnp.sum(kept * back, axis=0, keepdims=True))
    return conv, dx, rows


def _tap_rows(rows: list, k: int, c: int) -> list:
    """The taps' gradient as the 8 rows of its operand: tap 0 first."""
    return rows[::-1] + [jnp.zeros((SUBLANES - k, c), jnp.float32)] * (k < SUBLANES)


def _fwd_kernel(x_ref, xp_ref, keep_ref, w_ref, y_ref, *, c, k):
    x, g, gp = _gate_products(x_ref, xp_ref, c)
    acc = _conv_sum(g, gp, keep_ref[0], w_ref[...], None, k)
    y_ref[0] = (x[:, c:2 * c] * acc).astype(y_ref.dtype)


def _bwd_kernel(x_ref, xp_ref, xn_ref, keep_ref, keepn_ref, dy_ref, dyn_ref, w_ref,
                dx_ref, dw_ref, *, c, k, last):
    x, g, gp = _gate_products(x_ref, xp_ref, c)
    keep, w = keep_ref[0], w_ref[...]
    dy = _f32(dy_ref[0])
    dc = dy * x[:, c:2 * c]
    # the 8 rows after: nothing past the sequence's end
    after = jnp.where(pl.program_id(1) < last, 1.0, 0.0)
    dcn = _f32(dyn_ref[0]) * _f32(xn_ref[0][:, c:2 * c]) * after
    conv, dg, taps_grad = _taps_back(g, gp, keep, keepn_ref[0], w, dc, dcn, k, True)
    dx_ref[0, :, :c] = (dg * x[:, 2 * c:]).astype(dx_ref.dtype)
    dx_ref[0, :, c:2 * c] = (dy * conv).astype(dx_ref.dtype)
    dx_ref[0, :, 2 * c:] = (dg * x[:, :c]).astype(dx_ref.dtype)
    dw_ref[0, 0] = jnp.concatenate(_tap_rows(taps_grad, k, c), axis=0)


def _specs(ts: int, s: int):
    """``(rows, before, after, vec)``: specs of a tile's own rows, of the 8
    rows before and after it (clamped at the sequence's ends, where ``keep``
    and ``last`` discount them) and of the taps' 8 rows, each ``width`` wide.
    Over a grid of (batch, tiles) the width is all there is; over a third
    axis, blocks of channels, ``blocked`` says whether the operand has them
    (``keep`` has not)."""
    per = ts // SUBLANES

    def at(ci, blocked):
        return ci[0] if ci and blocked else 0

    def rows(width, blocked=True):
        return pl.BlockSpec((1, ts, width),
                            lambda bi, ti, *ci: (bi, ti, at(ci, blocked)))

    def before(width, blocked=True):
        return pl.BlockSpec(
            (1, SUBLANES, width),
            lambda bi, ti, *ci: (bi, jnp.maximum(ti * per - 1, 0), at(ci, blocked)))

    def after(width, blocked=True):
        return pl.BlockSpec(
            (1, SUBLANES, width),
            lambda bi, ti, *ci: (bi, jnp.minimum((ti + 1) * per, s // SUBLANES - 1),
                                 at(ci, blocked)))

    def vec(width):
        return pl.BlockSpec((SUBLANES, width), lambda bi, ti, *ci: (0, at(ci, True)))

    return rows, before, after, vec


def _params(grid_axes: int = 2):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * grid_axes,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _padded_taps(taps):
    return jnp.pad(_f32(taps), ((0, SUBLANES - taps.shape[0]), (0, 0)))


def _forward(bcz, taps, keep, ts, interpret):
    b, s, c3 = bcz.shape
    c, k = c3 // 3, taps.shape[0]
    rows, before, _, vec = _specs(ts, s)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, k=k),
        name="conv_gate_fwd",
        grid=(b, s // ts),
        in_specs=[rows(c3), before(c3), rows(SUBLANES), vec(c)],
        out_specs=rows(c),
        out_shape=jax.ShapeDtypeStruct((b, s, c), bcz.dtype),
        compiler_params=_params(),
        interpret=interpret,
    )(bcz, bcz, keep, _padded_taps(taps))


def _backward(bcz, taps, keep, dy, ts, interpret):
    b, s, c3 = bcz.shape
    c, k = c3 // 3, taps.shape[0]
    tiles = s // ts
    rows, before, after, vec = _specs(ts, s)
    # the backward rule is traced apart from the call: it names its scope itself
    with jax.named_scope("conv_gate"):
        dx, dw = pl.pallas_call(
            functools.partial(_bwd_kernel, c=c, k=k, last=tiles - 1),
            name="conv_gate_bwd",
            grid=(b, tiles),
            in_specs=[rows(c3), before(c3), after(c3), rows(SUBLANES), after(SUBLANES),
                      rows(c), after(c), vec(c)],
            out_specs=[rows(c3),
                       pl.BlockSpec((1, 1, SUBLANES, c), lambda bi, ti: (bi, ti, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(bcz.shape, bcz.dtype),
                       jax.ShapeDtypeStruct((b, tiles, SUBLANES, c), jnp.float32)],
            compiler_params=_params(),
            interpret=interpret,
        )(bcz, bcz, bcz, keep, keep, dy, dy, _padded_taps(taps))
        return dx, jnp.sum(dw, axis=(0, 1))[:k].astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(bcz, taps, keep, ts, interpret):
    return _forward(bcz, taps, keep, ts, interpret)


def _conv_fwd(bcz, taps, keep, ts, interpret):
    return _forward(bcz, taps, keep, ts, interpret), (bcz, taps, keep)


def _conv_bwd(ts, interpret, res, dy):
    bcz, taps, keep = res
    dx, dw = _backward(bcz, taps, keep, dy, ts, interpret)
    return dx, dw, jnp.zeros_like(keep)


_conv.defvjp(_conv_fwd, _conv_bwd)


_ROWS = P(DATA_AXES, None, None)


def _per_batch_shard(fn, operands, specs):
    """``fn(*operands)`` made legal on a mesh (a Mosaic call is not
    partitioned while a mesh axis is automatic): per shard of the batch
    (``_ROWS``), what ``specs`` says whole; a token needs nothing of another
    batch row, so the body holds no collective."""
    mesh, manual = shd.region_mesh()
    if mesh is None:
        return fn(*operands)
    return shd.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=_ROWS,
        axis_names=frozenset(mesh.axis_names) - manual if manual else frozenset(),
        check_vma=False)(*operands)


def _on_mesh(bcz, taps, keep, ts, interpret):
    return _per_batch_shard(lambda x, w, kp: _conv(x, w, kp, ts, interpret),
                            (bcz, taps, keep), (_ROWS, P(None, None), _ROWS))


def gated_short_conv(bcz: jax.Array, taps: jax.Array, *,
                     attention_mask: Optional[jax.Array] = None,
                     segment_ids: Optional[jax.Array] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``bcz [b, s, 3 x c]`` (the projection: B, C, z), ``taps [K, c]`` ->
    ``y [b, s, c]`` in ``bcz``'s dtype; the products and the taps' sum in
    float32.  ``attention_mask``, ``segment_ids``: ``[b, s]`` or None.
    ``interpret`` defaults to True off the TPU, so that tests run on the CPU
    (there any width goes; on a TPU the channels are whole lanes)."""
    b, s, c3 = bcz.shape
    k, c = taps.shape
    if c3 != 3 * c:
        raise ValueError(f"gated_short_conv: the projection is {c3} wide, "
                         f"want 3 x {c} (B ; C ; z)")
    if not 1 <= k <= MAX_TAPS:
        raise ValueError(f"gated_short_conv: {k} taps; the kernels' halo is one block of "
                         f"{SUBLANES} rows, so 1 to {MAX_TAPS} taps")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"   # jaxlint: disable=JL102
    if not interpret and c % LANES:
        raise ValueError(f"gated_short_conv: {c} channels; on a TPU the kernels slice the "
                         f"projection's thirds at lane boundaries (multiples of {LANES})")
    ts = _tile_rows(s, c)
    pad = -s % ts
    with jax.named_scope("conv_gate"):   # telemetry.spans.FAMILY_SCOPES
        keep = _keep(b, s, k, attention_mask, segment_ids)
        if pad:   # whole tiles: rows past the end see nothing and are cut off again
            bcz = jnp.pad(bcz, ((0, 0), (0, pad), (0, 0)))
            keep = jnp.pad(keep, ((0, 0), (0, pad), (0, 0)))
        return _on_mesh(bcz, taps, keep, ts, interpret)[:, :s]


# ---------------------------------------------------------------------------
# the plain convolution with a bias and ``silu`` (a Mamba-2 mixer's)
# ---------------------------------------------------------------------------

#: ``run_summary.json``'s ``mamba_conv.way``: how ``causal_conv`` is computed
CONV_WAY = "pallas"
#: channels of a tile of ``causal_conv`` (the gated middle's tiles hold all of
#: a layer's channels, three times; 6144 channels at once would leave 80 rows)
_CONV_CHANNELS = 2048


def _act(acc, silu: bool):
    return acc * jax.nn.sigmoid(acc) if silu else acc


def _cc_fwd_kernel(x_ref, xp_ref, keep_ref, w_ref, b_ref, y_ref, *, k, silu):
    acc = _conv_sum(_f32(x_ref[0]), _f32(xp_ref[0]), keep_ref[0], w_ref[...],
                    b_ref[0:1], k)
    y_ref[0] = _act(acc, silu).astype(y_ref.dtype)


def _cc_bwd_kernel(x_ref, xp_ref, xn_ref, keep_ref, keepn_ref, dy_ref, dyn_ref, w_ref, b_ref,
                   dx_ref, dw_ref, *, k, silu, last):
    x, xp, xn = _f32(x_ref[0]), _f32(xp_ref[0]), _f32(xn_ref[0])
    keep, keepn, w, bias = keep_ref[0], keepn_ref[0], w_ref[...], b_ref[0:1]

    def through_act(dy, acc):
        if not silu:
            return dy
        sig = jax.nn.sigmoid(acc)
        return dy * (sig * (1.0 + acc * (1.0 - sig)))

    dacc = through_act(_f32(dy_ref[0]), _conv_sum(x, xp, keep, w, bias, k))
    # the 8 rows after (their sum reads this tile's last rows): nothing past
    # the sequence's end
    after = jnp.where(pl.program_id(1) < last, 1.0, 0.0)
    daccn = through_act(_f32(dyn_ref[0]),
                        _conv_sum(xn, x[-SUBLANES:], keepn, w, bias, k)) * after
    _, dx, rows = _taps_back(x, xp, keep, keepn, w, dacc, daccn, k, False)
    dx_ref[0] = dx.astype(dx_ref.dtype)
    c = x.shape[1]
    # rows 0 .. k - 1 the taps' gradient, row 8 the bias's
    dw_ref[0, 0] = jnp.concatenate(
        _tap_rows(rows, k, c)
        + [jnp.sum(dacc, axis=0, keepdims=True), jnp.zeros((SUBLANES - 1, c), jnp.float32)],
        axis=0)


def _cc_forward(x, taps, bias, keep, ts, tc, silu, interpret):
    b, s, c = x.shape
    rows, before, _, vec = _specs(ts, s)
    return pl.pallas_call(
        functools.partial(_cc_fwd_kernel, k=taps.shape[0], silu=silu),
        name="mamba_conv_fwd",
        grid=(b, s // ts, c // tc),
        in_specs=[rows(tc), before(tc), rows(SUBLANES, False), vec(tc), vec(tc)],
        out_specs=rows(tc),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(3),
        interpret=interpret,
    )(x, x, keep, _padded_taps(taps), _padded_taps(bias[None]))


def _cc_backward(x, taps, bias, keep, dy, ts, tc, silu, interpret):
    b, s, c = x.shape
    k, tiles = taps.shape[0], s // ts
    rows, before, after, vec = _specs(ts, s)
    # the backward rule is traced apart from the call: it names its scope itself
    with jax.named_scope("mamba_conv"):
        dx, dw = pl.pallas_call(
            functools.partial(_cc_bwd_kernel, k=k, silu=silu, last=tiles - 1),
            name="mamba_conv_bwd",
            grid=(b, tiles, c // tc),
            in_specs=[rows(tc), before(tc), after(tc), rows(SUBLANES, False),
                      after(SUBLANES, False), rows(tc), after(tc), vec(tc), vec(tc)],
            out_specs=[rows(tc), pl.BlockSpec((1, 1, 2 * SUBLANES, tc),
                                              lambda bi, ti, ci: (bi, ti, 0, ci))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((b, tiles, 2 * SUBLANES, c), jnp.float32)],
            compiler_params=_params(3),
            interpret=interpret,
        )(x, x, x, keep, keep, dy, dy, _padded_taps(taps), _padded_taps(bias[None]))
        dw = jnp.sum(dw, axis=(0, 1))
        return dx, dw[:k].astype(taps.dtype), dw[SUBLANES].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _cc(x, taps, bias, keep, ts, tc, silu, interpret):
    return _cc_forward(x, taps, bias, keep, ts, tc, silu, interpret)


def _cc_fwd(x, taps, bias, keep, ts, tc, silu, interpret):
    return _cc_forward(x, taps, bias, keep, ts, tc, silu, interpret), (x, taps, bias, keep)


def _cc_bwd(ts, tc, silu, interpret, res, dy):
    x, taps, bias, keep = res
    return (*_cc_backward(x, taps, bias, keep, dy, ts, tc, silu, interpret),
            jnp.zeros_like(keep))


_cc.defvjp(_cc_fwd, _cc_bwd)


def causal_conv(x: jax.Array, taps: jax.Array, bias: Optional[jax.Array] = None, *,
                silu: bool = False, attention_mask: Optional[jax.Array] = None,
                segment_ids: Optional[jax.Array] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """``x [b, s, c]``, ``taps [K, c]`` (the taps lead, as above), ``bias [c]``
    or None -> ``y_t = act(sum_{j < K} w[j] x_{t - (K - 1) + j} + bias)`` in
    ``x``'s dtype, ``x`` zero before the sequence's start, ``act`` ``silu`` or
    nothing; products, sum and ``silu`` in float32, rounded once.  Masks as
    ``gated_short_conv``'s (``_keep``): a padded position counts as zero, the
    taps are cut at a document's start.

    One way (``CONV_WAY``): a Pallas pair over tiles of ``[256 rows,
    _CONV_CHANNELS]`` with the gated middle's halo of 8 rows, index clamps and
    ``keep`` operand; forward ``x`` read and ``y`` written, backward ``x`` and
    ``y``'s cotangent read and ``x``'s written, the sum run again from ``x``:
    nothing but the operands is kept.  Left to XLA the chain (four shifted
    multiply-adds, the bias, ``silu``) moved 7 x its bytes forward and 9 x
    forward and backward at the benchmark's shape (PERF.md section 6, PR 46)."""
    b, s, c = x.shape
    k = taps.shape[0]
    if taps.shape[1] != c:
        raise ValueError(f"causal_conv: taps for {taps.shape[1]} channels, x has {c}")
    if not 1 <= k <= MAX_TAPS:
        raise ValueError(f"causal_conv: {k} taps; the kernels' halo is one block of "
                         f"{SUBLANES} rows, so 1 to {MAX_TAPS} taps")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"   # jaxlint: disable=JL102
    tc = next(n for n in (_CONV_CHANNELS, 1024, 512, 256, LANES, c) if c % n == 0)
    if not interpret and tc % LANES:
        raise ValueError(f"causal_conv: {c} channels; on a TPU the kernels' tiles are "
                         f"whole lanes (multiples of {LANES})")
    if bias is None:
        bias = jnp.zeros((c,), taps.dtype)
    ts = _tile_rows(s, tc)
    pad = -s % ts
    keep = _keep(b, s, k, attention_mask, segment_ids)
    if pad:   # whole tiles: rows past the end see nothing and are cut off again
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        keep = jnp.pad(keep, ((0, 0), (0, pad), (0, 0)))
    return _per_batch_shard(
        lambda xx, w, bb, kp: _cc(xx, w, bb, kp, ts, tc, silu, interpret),
        (x, taps, bias, keep), (_ROWS, P(None, None), P(None), _ROWS))[:, :s]
