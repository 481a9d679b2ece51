"""Attention whose keys a learned indexer chooses (DeepSeek Sparse Attention,
arXiv:2512.02556; ``models/keye.py``): a second, cheap attention scores every
causal pair, each query keeps its ``topk`` best-scored keys, the main
attention's softmax runs over those alone, and a KL loss of its own trains the
indexer towards the main attention's probabilities.

With ``h`` the normed layer input, its gradient stopped (``t`` a query, ``s <=
t`` a key, ``Hi`` index heads of ``di`` dims, ONE index key a token):

    qI = rope(h WqI) [T, Hi, di]    kI = rope(LayerNorm(h WkI)) [T, di]
    w  = h Ww [T, Hi]
    I[t, s] = (Hi di)^(-1/2) sum_j w[t, j] relu(qI[t, j] . kI[s])     float32
    S_t = the min(topk, visible keys of t) keys of largest I[t, s]; ties go
          to the lower index; a constant (no gradient passes through it)
    o_t = concat_heads(sum_{s in S_t} A[t, head, s] v_s),
          A[t, head, .] = softmax_{s in S_t}(q_t,head . k_s / sqrt(d))
    p_t = mean_head A[t, head, .], gradient stopped
    L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, .])

Visible keys are the causal ones of the query's own document
(``segment_ids``) that are no padding (``attention_mask``), so a key outside
them is never chosen.  ``L_I`` reaches the indexer's leaves only (its inputs
are detached) and nothing else reaches them (the selection is a constant).

How it runs.  Whatever the way, the sequence is walked in chunks of
``q_chunk`` queries by a Python loop, so chunk ``i`` sees the keys ``[0, (i +
1) q_chunk)`` as a static shape: the index scores of a chunk (scope
``attention/indexer``, never kept past their use) and its selection
(``attention/select``; none while the chunk's keys are no more than ``topk``:
that part of the layer IS dense causal attention).  Then (``WAYS``:
``flash_mask`` where ``model.fusions.flash_attention`` is set, as the kernels
of every family are asked for; ``run_summary.json`` says which a trace took):

- ``flash_mask``: the chunks' selections laid together are ONE int8 mask
  ``[b, T, T]``, an operand in ``(bq, bkv)`` blocks of three kernels that walk
  the causal band as ``ops/flash_attention.py``'s do (its ``_band`` / ``_walk``,
  read and not changed; bodies of their own under the same scopes and operand
  order, so a trace reduction finds them as the attention kernels they are):
  forward (``o``, ``lse``, kept across a rematerialized layer), dq, dkv.  The
  mask holds causality, documents and padding already; a block whose rows
  selected none of its keys is predicated off; every pair of a visited block
  is formed and the unselected masked.  ``L_I`` and its gradient are taken
  together in the forward (``_indexer_loss``), since every input of the loss
  is detached and known there: the chunks are walked once more, and of each
  are taken ``p`` from the kernels' own ``lse``
  (a fourth kernel, ``dsa_probs``, sums ``exp(s - lse)`` over the heads on
  the kept pairs: one more ``Q K^T``, under ``attention/indexer_loss``), the
  KL of ``p`` and the chunk's index scores (their value handed on from the
  selection), the KL's gradient on those scores and that gradient pulled
  back through the scores (the heads' products formed again for it, under
  ``attention/indexer``, and alive for one chunk) into ``d L_I / d (qI, kI, w)``,
  float32.  That gradient is the only residual of a ``custom_vjp`` that ties
  it to the loss (``_with_gradient``; ``[b, T, Hi, di]`` + ``[b, T, di]`` +
  ``[b, T, Hi]``: 72 MB a layer at two sequences of 8192), named
  (``KEPT_NAMES``) so that a layer rematerialized under ``full`` keeps it
  across its rerun as ``o`` and ``lse`` are kept; the backward rule
  multiplies it by the loss's cotangent.  The rerun rebuilds the index scores
  and the selection (the mask is an operand of dq and dkv) and nothing of the
  loss: ``dsa_probs`` runs once a chunk in a step, not twice
  (``run_summary.json``: ``sparse_attention.loss_passes_per_layer_application``).
- ``xla_chunks``: where the shapes do not tile the kernels (toy widths on the
  CPU mesh; on a TPU that raises): masked scores, softmax, values, ``p`` and
  the KL of a chunk in one rematerialised function of XLA operations, one
  sequence at a time, ``L_I``'s gradient by plain autodiff (tests/test_keye.py
  holds the two ways equal).  ``[heads, T, T]`` is never whole in memory
  either way.

The threshold ``tau_t``, the ``k``-th largest of a row, is exact
(``THRESHOLD``): a kernel that holds a block of rows in VMEM and bisects the
float's bit pattern (32 compare-and-count passes, then 13 more over the key
index where ties at ``tau`` must be cut: ties go to the lower index).  It gives
the set of the reference's stable sort, bit for bit (tests/test_keye.py).

Precision, as it runs: the indexer's three projections, the LayerNorm, the
rope, the heads' weighted sum, the threshold and ``L_I`` are float32; the ONE
matmul ``qI . kI`` takes its operands at the policy's compute dtype (bfloat16
under ``mixed_precision``) and accumulates in float32.  No limit of the
benchmark's ``correct`` holds the selection's precision (norms do not see which
of two all-but-tied keys was kept): tests/test_keye.py's comparison of the
selected sets holds the threshold's exactness and the tie rule, on the CPU, and
nothing holds the operands' precision (PERF.md section 7).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd

WAYS = ("flash_mask", "xla_chunks")
#: how the k-th largest of a row is found (``run_summary.json`` says it)
THRESHOLD = "pallas_bisect"
NEG_INF = fa.NEG_INF
#: rows of index scores a step of the selection kernel holds in VMEM
SELECT_ROWS = 64
_INT_MIN = -(2 ** 31)
#: what a masked kernel may hold in VMEM: the flash kernels' tiles with a
#: ``(bq, bkv)`` block of the mask beside them pass the 16 MiB a kernel gets
#: unasked (dkv at 512 x 2048 x 128 dims: 18.33 MiB); a v5e has 128
_KERNEL_VMEM = 32 * 2 ** 20
#: ``L_I``'s gradient on the indexer's three operands, taken in the layer's
#: forward (``_indexer_loss``) and named as a residual: a layer rematerialized
#: under a policy that holds these names (``models/keye.py`` hands them to
#: ``llama.checkpoint_layer``) keeps them, and its rerun forms nothing of the loss
KEPT_NAMES = ("dsa_loss_d_qi", "dsa_loss_d_ki", "dsa_loss_d_wi")


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """The source's ``sa_config`` block.  ``q_chunk_size`` / ``kv_chunk_size``
    are tiles of the source's computation and enter no equation; the first is
    the chunk of queries this implementation walks by."""
    topk: int = 2048
    index_heads: int = 16
    index_head_dim: int = 64
    index_kv_heads: int = 1
    q_chunk: int = 512
    way: str = "flash_mask"
    norm_eps: float = 1e-6

    @classmethod
    def from_config(cls, sa: Mapping[str, Any], *, norm_eps: float,
                    way: str = "flash_mask") -> "SparseAttentionConfig":
        """``way``: the caller's (``models/keye.py``: the kernels where
        ``model.fusions.flash_attention`` is set, as for every family)."""
        sa = dict(sa or {})
        known = {"topk", "indexer_num_heads", "indexer_head_dim", "indexer_num_kv_heads",
                 "q_chunk_size", "kv_chunk_size"}
        if set(sa) - known:
            raise ValueError(f"model.sa_config: unknown keys {sorted(set(sa) - known)} "
                             f"(known: {sorted(known)})")
        cfg = cls(topk=int(sa.get("topk", 2048)),
                  index_heads=int(sa.get("indexer_num_heads", 16)),
                  index_head_dim=int(sa.get("indexer_head_dim", 64)),
                  index_kv_heads=int(sa.get("indexer_num_kv_heads", 1)),
                  q_chunk=int(sa.get("q_chunk_size", 512)),
                  way=way, norm_eps=float(norm_eps))
        if cfg.index_kv_heads != 1:
            raise ValueError(f"model.sa_config.indexer_num_kv_heads {cfg.index_kv_heads}: "
                             "wired for one index key a token, shared by the index heads")
        if cfg.way not in WAYS:
            raise ValueError(f"sparse attention: way {cfg.way!r} (known: {WAYS})")
        if cfg.topk < 1 or cfg.index_heads < 1 or cfg.index_head_dim % 2:
            raise ValueError(f"model.sa_config: want topk >= 1, indexer_num_heads >= 1 and "
                             f"an even indexer_head_dim, got {sa}")
        return cfg

    def facts(self) -> dict:
        """``run_summary.json``'s ``sparse_attention`` block."""
        return {"topk": self.topk, "index_heads": self.index_heads,
                "index_head_dim": self.index_head_dim, "way": self.way,
                "threshold": THRESHOLD, "q_chunk": self.q_chunk}


# ---------------------------------------------------------------------------
# the indexer's leaves and its three projections
# ---------------------------------------------------------------------------


def init_indexer(key: jax.Array, hidden: int, cfg: SparseAttentionConfig, *,
                 dtype=jnp.float32, stddev: float = 0.02):
    """``wq [hidden, Hi di]``, ``wk [hidden, di]``, ``weights [hidden, Hi]``
    and the key's LayerNorm (scale 1, bias 0)."""
    kq, kk, kw = jax.random.split(key, 3)

    def linear(k, n_out):
        return linear_ops.init_linear(k, hidden, n_out, shard="replicated", dtype=dtype,
                                      stddev=stddev)[0]

    return {"wq": linear(kq, cfg.index_heads * cfg.index_head_dim),
            "wk": linear(kk, cfg.index_head_dim),
            "weights": linear(kw, cfg.index_heads),
            "k_norm": norm_ops.init_layer_norm(cfg.index_head_dim, dtype=dtype)[0]}


def indexer_inputs(lp, h: jax.Array, cos, sin, cfg: SparseAttentionConfig):
    """``h [b, T, hidden]`` (detached here) -> ``(qI [b, T, Hi, di], kI [b, T,
    di], w [b, T, Hi])`` in float32, as the router's scores are: the leaves
    arrive as they are kept, not cast to the compute dtype."""
    b, t, _ = h.shape
    hf = jax.lax.stop_gradient(h).astype(jnp.float32)  # jaxlint: disable=JL106

    def proj(name):
        return hf @ lp[name]["w"].astype(jnp.float32)  # jaxlint: disable=JL106

    q = proj("wq").reshape(b, t, cfg.index_heads, cfg.index_head_dim)
    k = norm_ops.apply_layer_norm(lp["k_norm"], proj("wk"), eps=cfg.norm_eps)
    q = rope_ops.apply_rope(q, cos, sin)
    k = rope_ops.apply_rope(k[:, :, None, :], cos, sin)[:, :, 0, :]
    return q, k, proj("weights")


def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array,
                 operand_dtype=jnp.float32) -> jax.Array:
    """``qi [b, c, Hi, di]``, ``ki [b, s, di]``, ``w [b, c, Hi]`` (float32)
    -> ``I [b, c, s]`` float32.  The one matmul takes its operands at
    ``operand_dtype`` (the policy's compute dtype: what the MXU rounds float32
    operands to at default precision anyway, said here) and accumulates in
    float32; the heads' weighted sum is elementwise, in float32."""
    dots = jnp.einsum("bchd,bsd->bhcs", qi.astype(operand_dtype), ki.astype(operand_dtype),
                      preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(qi.shape[2] * qi.shape[3])
    weights = jnp.swapaxes(w, 1, 2)[..., None]                        # [b, Hi, c, 1]
    return jnp.sum(jax.nn.relu(dots) * weights, axis=1) * scale


# ---------------------------------------------------------------------------
# the selection: each row's k-th largest, exactly
# ---------------------------------------------------------------------------


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' order (-0.0 is 0.0,
    as it is to a comparison of floats)."""
    bits = jnp.where(x == 0.0, jnp.int32(0), jax.lax.bitcast_convert_type(x, jnp.int32))
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_kernel(x_ref, o_ref, *, topk: int):
    """``x_ref [1, rows, s]`` float32, ``-inf`` where a key is not visible ->
    ``o_ref`` int32, 1 on each row's ``min(topk, visible)`` largest, ties to
    the lower index."""
    x = x_ref[0]
    rows, s = x.shape
    visible = x > -jnp.inf
    keys = _ordered_bits(x)
    k = jnp.minimum(jnp.sum(visible.astype(jnp.int32), axis=-1, keepdims=True), topk)

    def count(mask):
        return jnp.sum(mask.astype(jnp.int32), axis=-1, keepdims=True)

    # the largest tau with at least k keys >= tau, bit by bit from the sign
    tau = jnp.where(count(keys >= 0) >= k, jnp.int32(0), jnp.int32(_INT_MIN))

    def bit_of_tau(i, tau):
        cand = tau | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(keys >= cand) >= k, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit_of_tau, tau)
    above = jnp.logical_and(keys > tau, visible)
    tied = jnp.logical_and(keys == tau, visible)
    need = k - count(above)          # how many of the tied keys belong
    o_ref[0] = jnp.logical_or(above, tied).astype(jnp.int32)

    @pl.when(jnp.any(count(tied) > need))
    def _cut_ties():
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
        bits = max(1, (s - 1).bit_length())

        # the least index m with `need` tied keys at or below it: the largest
        # m with fewer than `need` below it
        def bit_of_index(i, m):
            cand = m + jnp.left_shift(jnp.int32(1), bits - 1 - i)
            return jnp.where(count(jnp.logical_and(tied, col < cand)) < need, cand, m)

        m = jax.lax.fori_loop(0, bits, bit_of_index, jnp.zeros((rows, 1), jnp.int32))
        o_ref[0] = jnp.logical_or(
            above, jnp.logical_and(tied, jnp.logical_and(col <= m, need > 0))
        ).astype(jnp.int32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"   # jaxlint: disable=JL102


def _select_pallas(x: jax.Array, topk: int) -> jax.Array:
    b, c, s = x.shape
    rows = math.gcd(c, SELECT_ROWS)
    with jax.named_scope("dsa_select"):
        out = pl.pallas_call(
            functools.partial(_select_kernel, topk=topk),
            name="dsa_select",
            grid=(b, c // rows),
            in_specs=[pl.BlockSpec((1, rows, s), lambda bi, ri: (bi, ri, 0))],
            out_specs=pl.BlockSpec((1, rows, s), lambda bi, ri: (bi, ri, 0)),
            out_shape=jax.ShapeDtypeStruct((b, c, s), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=64 * 2 ** 20),
            interpret=_interpret(),
        )(x)
    return out > 0


def _per_shard(fn, in_specs, out_specs):
    """``fn`` on each shard of the batch where a mesh is active (a Mosaic call
    is refused under GSPMD, and no row needs another: as ``ops.attention``'s
    flash call), else ``fn``."""
    mesh, manual = shd.region_mesh()
    if mesh is None:
        return fn
    axis_names = frozenset(mesh.axis_names) - manual if manual else frozenset()
    return shd.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=axis_names, check_vma=False)


_ROWS3, _ROWS4 = P(shd.DATA_AXES, None, None), P(shd.DATA_AXES, None, None, None)


def select(scores: jax.Array, visible: jax.Array, cfg: SparseAttentionConfig) -> jax.Array:
    """``scores [b, c, s]`` and the pairs a rule shows ``[b, c, s]`` -> the
    selected pairs (bool), a constant."""
    x = jax.lax.stop_gradient(scores).astype(jnp.float32)  # jaxlint: disable=JL106
    # -0.0 (every index head silent under negative weights) ties with 0.0
    x = jnp.where(visible, jnp.where(x == 0.0, 0.0, x), -jnp.inf)
    return _per_shard(functools.partial(_select_pallas, topk=cfg.topk),
                      (_ROWS3,), _ROWS3)(x)


# ---------------------------------------------------------------------------
# one chunk of queries against the keys it selected
# ---------------------------------------------------------------------------


def _attend_chunk(q, k, v, scores, sel, softmax_dtype):
    """One sequence: ``q [c, nh, d]``, ``k`` / ``v`` ``[s, nkv, d]``, the index
    scores and the selection ``[c, s]`` -> ``(o [c, nh, d], KL [c])``."""
    c, nh, d = q.shape
    nkv = k.shape[1]
    qg = q.reshape(c, nkv, nh // nkv, d)
    logits = jnp.einsum("cngd,snd->ngcs", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    logits = jnp.where(sel, logits.astype(softmax_dtype), NEG_INF)
    # a row that selected nothing (a padded query) attends to nothing
    probs = jnp.where(sel, jax.nn.softmax(logits, axis=-1), 0.0)
    o = jnp.einsum("ngcs,snd->cngd", probs.astype(v.dtype), v)
    with jax.named_scope("indexer_loss"):
        p = jax.lax.stop_gradient(
            jnp.mean(probs.astype(jnp.float32), axis=(0, 1)))  # jaxlint: disable=JL106
        kl = _kl_rows(p, scores, sel)
    return o.reshape(c, nh, d), kl


def _kl_rows(p, scores, sel):
    """``KL(p || softmax_sel(scores))`` of every row: ``p [..., c, s]`` the
    main attention's head-mean probabilities (detached, zero off ``sel``)."""
    log_q = jax.nn.log_softmax(
        jnp.where(sel, scores.astype(jnp.float32), NEG_INF), axis=-1)  # jaxlint: disable=JL106
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0),
                   axis=-1)


# ---------------------------------------------------------------------------
# the flash kernels with the selection as a mask operand (way ``flash_mask``)
# ---------------------------------------------------------------------------
#
# ``ops/flash_attention.py``'s band walk over the causal blocks (its ``_band``,
# ``_walk`` and spans, read and not changed) with kernel bodies of their own:
# the mask inside a block is the operand ``sel [b, T, T]`` int8 in ``(bq, bkv)``
# blocks, which holds causality, documents and padding already, so no rule is
# applied inside a block; a block whose rows selected none of its keys is
# predicated off.  Same operand order as the flash kernels (q first, ``[b,
# heads, s, d]``; fwd returns ``(o, float32 lse)``, dq one array, dkv two) under
# the same scopes, so a trace reduction finds them as the attention kernels
# they are.  Every causal pair of a visited block is formed and the unselected
# ones masked: the kernels do 1 / kept-share of the required work.


def _keep(sel_ref):
    return sel_ref[0].astype(jnp.int32) != 0


def _sel_fwd_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                    sm_scale, band):
    qi, j = pl.program_id(2), pl.program_id(3)
    _, in_band, _ = fa._walk(fa._kv_span, band, qi, j)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    keep = _keep(sel_ref)

    @pl.when(jnp.logical_and(in_band, jnp.any(keep)))
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == band.kv - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # a row that selected nothing (a padded query): output 0, lse NEG_INF
        row_visible = m_scr[:, :1] > NEG_INF / 2
        o_ref[0, 0] = jnp.where(row_visible, acc_scr[:] / l_safe, 0.0).astype(o_ref.dtype)
        lse = jnp.where(row_visible, m_scr[:, :1] + jnp.log(l_safe), NEG_INF)
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], fa.SUBLANES))


def _probs(q_ref, k_ref, lse_ref, keep, sm_scale):
    """``exp(s - lse)`` on the kept pairs of a block, 0 elsewhere (a row with
    no kept key anywhere carries ``lse = NEG_INF``)."""
    s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    lse = lse_ref[0, 0][:, :1]
    return jnp.where(jnp.logical_and(keep, lse > NEG_INF / 2), jnp.exp(s - lse), 0.0)


def _sel_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref, dq_ref, acc_scr,
                   *, sm_scale, band):
    qi, j = pl.program_id(2), pl.program_id(3)
    _, in_band, _ = fa._walk(fa._kv_span, band, qi, j)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    keep = _keep(sel_ref)

    @pl.when(jnp.logical_and(in_band, jnp.any(keep)))
    def _compute():
        p = _probs(q_ref, k_ref, lse_ref, keep, sm_scale)
        dp = jax.lax.dot_general(
            do_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),  # jaxlint: disable=JL106
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * sm_scale
        acc_scr[:] += jax.lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),  # jaxlint: disable=JL106
            preferred_element_type=jnp.float32)

    @pl.when(j == band.kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _sel_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *, sm_scale, band, group):
    ki, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    _, in_band, _ = fa._walk(fa._q_span, band, ki, j)

    @pl.when(jnp.logical_and(g == 0, j == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    keep = _keep(sel_ref)

    @pl.when(jnp.logical_and(in_band, jnp.any(keep)))
    def _compute():
        p = _probs(q_ref, k_ref, lse_ref, keep, sm_scale)
        do = do_ref[0, 0].astype(jnp.float32)  # jaxlint: disable=JL106
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0].astype(jnp.float32),  # jaxlint: disable=JL106
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),  # jaxlint: disable=JL106
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(g == group - 1, j == band.q - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _chunk_probs_kernel(sel_ref, q_ref, k_ref, lse_ref, o_ref, *, sm_scale):
    """The sum over the heads (innermost) of ``exp(s - lse)`` on the kept
    pairs of one chunk of queries and one block of keys, accumulated in the
    block of the result."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    keep = _keep(sel_ref)

    @pl.when(jnp.any(keep))
    def _compute():
        o_ref[0] += _probs(q_ref, k_ref, lse_ref, keep, sm_scale)


def _tiles(t: int, d: int, dtype, chunk: int) -> tuple:
    """``(bq, bkv)`` of the masked kernels: the flash kernels' own for the
    shape, the query tile no longer than a chunk of the selection."""
    bq, bkv = fa._block_sizes(t, t, None, None, dtype, d)
    return min(bq, chunk), bkv


def _sel_forward(q, k, v, sel, bq, bkv, interpret):
    """q ``[b, nh, t, d]``, k / v ``[b, nkv, t, d]``, sel ``[b, t, t]`` int8 ->
    ``(o, lse [b, nh, t, SUBLANES])``."""
    b, nh, t, d = q.shape
    group = nh // k.shape[1]
    band = fa._band(bq, bkv, t // bq, t // bkv, True, None, 0)

    def kv_at(qi, j):
        return fa._walk(fa._kv_span, band, qi, j)[2]

    def kv_spec():
        return pl.BlockSpec((1, 1, bkv, d),
                            lambda bi, hi, qi, j: (bi, hi // group, kv_at(qi, j), 0))

    def q_spec(width):
        return pl.BlockSpec((1, 1, bq, width), lambda bi, hi, qi, j: (bi, hi, qi, 0))

    with jax.named_scope("flash_fwd"):
        return pl.pallas_call(
            functools.partial(_sel_fwd_kernel, sm_scale=1.0 / math.sqrt(d), band=band),
            name="flash_sel_fwd",
            grid=(b, nh, t // bq, band.kv),
            in_specs=[q_spec(d), kv_spec(), kv_spec(),
                      pl.BlockSpec((1, bq, bkv), lambda bi, hi, qi, j: (bi, qi, kv_at(qi, j)))],
            out_specs=[q_spec(d), q_spec(fa.SUBLANES)],
            out_shape=[jax.ShapeDtypeStruct((b, nh, t, d), q.dtype),
                       jax.ShapeDtypeStruct((b, nh, t, fa.SUBLANES), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bq, fa.LANES), jnp.float32),
                            pltpu.VMEM((bq, fa.LANES), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_KERNEL_VMEM),
            interpret=interpret,
        )(q, k, v, sel)


def _sel_backward(q, k, v, sel, o, lse, g, bq, bkv, interpret):
    b, nh, t, d = q.shape
    nkv = k.shape[1]
    group = nh // nkv
    band = fa._band(bq, bkv, t // bq, t // bkv, True, None, 0)
    delta = fa._delta_rows(g, o, None)
    sm_scale = 1.0 / math.sqrt(d)

    def kv_at(qi, j):
        return fa._walk(fa._kv_span, band, qi, j)[2]

    def q_at(ki, j):
        return fa._walk(fa._q_span, band, ki, j)[2]

    def dq_q(width):
        return pl.BlockSpec((1, 1, bq, width), lambda bi, hi, qi, j: (bi, hi, qi, 0))

    dq_kv = pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, j: (bi, hi // group, kv_at(qi, j), 0))
    operands = (q, k, v, g, lse, delta, sel)
    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_sel_dq_kernel, sm_scale=sm_scale, band=band),
            name="flash_sel_dq",
            grid=(b, nh, t // bq, band.kv),
            in_specs=[dq_q(d), dq_kv, dq_kv, dq_q(d), dq_q(fa.SUBLANES), dq_q(fa.SUBLANES),
                      pl.BlockSpec((1, bq, bkv), lambda bi, hi, qi, j: (bi, qi, kv_at(qi, j)))],
            out_specs=dq_q(d),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_KERNEL_VMEM),
            interpret=interpret,
        )(*operands)

    def dkv_q(width):
        return pl.BlockSpec((1, 1, bq, width),
                            lambda bi, kh, ki, g_, j: (bi, kh * group + g_, q_at(ki, j), 0))

    dkv_kv = pl.BlockSpec((1, 1, bkv, d), lambda bi, kh, ki, g_, j: (bi, kh, ki, 0))
    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_sel_dkv_kernel, sm_scale=sm_scale, band=band, group=group),
            name="flash_sel_dkv",
            grid=(b, nkv, t // bkv, group, band.q),
            in_specs=[dkv_q(d), dkv_kv, dkv_kv, dkv_q(d), dkv_q(fa.SUBLANES),
                      dkv_q(fa.SUBLANES),
                      pl.BlockSpec((1, bq, bkv),
                                   lambda bi, kh, ki, g_, j: (bi, q_at(ki, j), ki))],
            out_specs=[dkv_kv, dkv_kv],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                            pltpu.VMEM((bkv, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_KERNEL_VMEM),
            interpret=interpret,
        )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_sel(q, k, v, sel, bq, bkv, interpret):
    """``(o, lse [b, nh, t])``; ``lse`` is for detached use (the indexer's
    loss): its cotangent is dropped."""
    o, lse = _sel_forward(q, k, v, sel, bq, bkv, interpret)
    return o, lse[..., 0]


def _flash_sel_fwd(q, k, v, sel, bq, bkv, interpret):
    """The kernel's outputs named as the flash kernels' are (``KEPT_NAMES``):
    a layer rematerialized under ``full`` keeps them and its rerun does not
    call the forward kernel again."""
    o, lse = _sel_forward(q, k, v, sel, bq, bkv, interpret)
    o = checkpoint_name(o, fa.KEPT_NAMES[0])
    lse = checkpoint_name(lse[..., 0], fa.KEPT_NAMES[1])
    return (o, lse), (q, k, v, sel, o, lse)


def _flash_sel_bwd(bq, bkv, interpret, res, g):
    q, k, v, sel, o, lse = res
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (fa.SUBLANES,))
    grads = _sel_backward(q, k, v, sel, o, lse, g[0], bq, bkv, interpret)
    return (*grads, np.zeros(sel.shape, dtype=jax.dtypes.float0))


_flash_sel.defvjp(_flash_sel_fwd, _flash_sel_bwd)


def _chunk_probs(sel, q, k, lse, interpret):
    """One chunk of queries: ``sel [b, c, s]`` int8, ``q [b, nh, c, d]``, ``k
    [b, nkv, s, d]`` (the keys before the chunk's end), ``lse [b, nh, c,
    SUBLANES]`` -> ``sum_head exp(s - lse)`` on the kept pairs, ``[b, c, s]``
    float32.  ``sel`` leads the operands, so the trace reduction that knows
    the attention kernels by their first operand does not take this one for
    one of them."""
    b, nh, c, d = q.shape
    s = k.shape[2]
    group = nh // k.shape[1]
    bkv = math.gcd(s, 512)
    tile = pl.BlockSpec((1, c, bkv), lambda bi, j, hi: (bi, 0, j))
    with jax.named_scope("dsa_probs"):
        return pl.pallas_call(
            functools.partial(_chunk_probs_kernel, sm_scale=1.0 / math.sqrt(d)),
            name="dsa_probs",
            grid=(b, s // bkv, nh),
            in_specs=[tile,
                      pl.BlockSpec((1, 1, c, d), lambda bi, j, hi: (bi, hi, 0, 0)),
                      pl.BlockSpec((1, 1, bkv, d), lambda bi, j, hi: (bi, hi // group, j, 0)),
                      pl.BlockSpec((1, 1, c, fa.SUBLANES), lambda bi, j, hi: (bi, hi, 0, 0))],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct((b, c, s), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_KERNEL_VMEM),
            interpret=interpret,
        )(sel, q, k, lse)


def _count(real):
    """The real queries of ``real [b, T]``, at least 1 (float32)."""
    return jnp.maximum(jnp.sum(real.astype(jnp.float32)), 1.0)  # jaxlint: disable=JL106


def _loss_and_grads(qi, ki, wi, scores, sels, q, k, lse, real, operand_dtype, interpret):
    """``L_I`` of the masked kernels' way and its gradient on ``(qi, ki, wi)``,
    a chunk of queries at a time: ``scores`` the chunks' index scores as the
    selection read them and ``sels`` their int8 masks (``[b, c, keys]`` each),
    ``q [b, nh, T, d]`` / ``k [b, nkv, T, d]`` and the forward kernel's ``lse
    [b, nh, T]``, ``real [b, T]``.  A chunk's ``p`` comes from ``dsa_probs``;
    the KL's gradient on its scores is ``(softmax_sel(I) sum(p) - p) / (real
    queries)`` on the real rows (the pullback of ``_kl_rows``); the scores'
    pullback, for which the heads' products are formed again and live for this
    chunk alone, turns it into the chunk's part of the three gradients."""
    t = real.shape[1]
    nh, chunk = q.shape[1], t // len(sels)
    scores_of = functools.partial(index_scores, operand_dtype=operand_dtype)
    probs = _per_shard(functools.partial(_chunk_probs, interpret=interpret),
                       (_ROWS3, _ROWS4, _ROWS4, _ROWS4), _ROWS3)
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (fa.SUBLANES,))
    n_real = _count(real)
    kls, d_qi, d_wi, d_ki = [], [], [], jnp.zeros_like(ki)
    for i, (m, chunk_scores) in enumerate(zip(sels, scores)):
        rows, s = slice(i * chunk, (i + 1) * chunk), (i + 1) * chunk
        sel = m != 0
        with jax.named_scope("indexer_loss"):
            # the main attention's probabilities from its own ``lse``: one more
            # pass over the chunk's pairs
            p = jnp.where(sel, probs(m, q[:, :, rows], k[:, :, :s], lse[:, :, rows]) / nh, 0.0)
            kl, pull_kl = jax.vjp(lambda x, p=p, sel=sel: _kl_rows(p, x, sel), chunk_scores)
            (d_scores,) = pull_kl(jnp.where(real[:, rows], 1.0 / n_real, 0.0))
        with jax.named_scope("indexer"):
            # (the value the pullback comes with is ``chunk_scores`` again: unused)
            _, pull_scores = jax.vjp(scores_of, qi[:, rows], ki[:, :s], wi[:, rows])
            dq, dk, dw = pull_scores(d_scores)
            d_ki = d_ki + jnp.pad(dk, ((0, 0), (0, t - s), (0, 0)))
        kls.append(kl)
        d_qi.append(dq)
        d_wi.append(dw)
    kl = jnp.sum(jnp.where(real, jnp.concatenate(kls, axis=1), 0.0)) / n_real
    return kl, (jnp.concatenate(d_qi, axis=1), d_ki, jnp.concatenate(d_wi, axis=1))


@jax.custom_vjp
def _with_gradient(loss, operands, grads):
    """``loss`` (a value formed from detached inputs) as a function of
    ``operands`` whose gradient is ``grads``, known already."""
    return loss


def _with_gradient_fwd(loss, operands, grads):
    """The gradient is the rule's only residual, named (``KEPT_NAMES``) so
    that a rematerialized layer keeps it across its rerun as the forward
    kernel's ``o`` and ``lse`` are kept; what formed it is then dead there."""
    return loss, tuple(checkpoint_name(g, name) for g, name in zip(grads, KEPT_NAMES))


def _with_gradient_bwd(grads, g):
    with jax.named_scope("indexer_loss"):
        return None, tuple(g * d for d in grads), None


_with_gradient.defvjp(_with_gradient_fwd, _with_gradient_bwd)


def _indexer_loss(qi, ki, wi, scores, sels, q, k, lse, real, operand_dtype, interpret):
    """``L_I``, the mean over the real queries of ``KL(p_t || softmax_{S_t}
    I[t, .])``, of the masked kernels' way.  Every input of it is detached
    and known in the forward, so its gradient on the indexer's three operands
    is taken there and then, once (``_loss_and_grads``; the Python of it is
    traced once too), and handed to the backward pass as a residual: the
    layer's rerun calls no ``dsa_probs`` and forms no KL.  ``scores``: the
    value of the chunks' ``index_scores`` of the same operands, which the op
    formed for the selection, so that the loss's value costs no pass of its
    own."""
    qd, kd, wd, scores, q, k, lse = jax.tree_util.tree_map(
        jax.lax.stop_gradient, (qi, ki, wi, scores, q, k, lse))
    kl, grads = _loss_and_grads(qd, kd, wd, scores, sels, q, k, lse, real, operand_dtype,
                                interpret)
    return _with_gradient(kl, (qi, ki, wi), grads)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


class _Chunk(NamedTuple):
    """One chunk of queries: its rows, how many keys it sees, its index scores
    ``[b, c, keys]``, the pairs it selected and the count of those a rule shows."""
    rows: slice
    keys: int
    scores: jax.Array
    sel: jax.Array
    shown: jax.Array


def way_for(cfg: SparseAttentionConfig, t: int, d: int, nh: int, nkv: int, dtype) -> str:
    """``flash_mask`` where the config asks for it and the shapes tile the
    kernels; else ``xla_chunks`` (toy widths on the CPU mesh; on a TPU a call
    that asked for the kernels and does not tile raises)."""
    if cfg.way != "flash_mask":
        return cfg.way
    chunk = math.gcd(t, cfg.q_chunk)
    bq, bkv = _tiles(t, d, dtype, chunk)
    if (fa._tileable(t, t, d, bq, bkv) and chunk % bq == 0 and chunk % fa.LANES == 0
            and nh % nkv == 0):
        return "flash_mask"
    if not _interpret():
        raise ValueError(
            f"sparse attention: seq {t}, heads of {d} and chunks of {chunk} queries do not "
            f"tile the masked flash kernels (tiles {bq} x {bkv}); set "
            "model.fusions.flash_attention: false for this model")
    return "xla_chunks"


def sparse_attention(q, k, v, qi, ki, wi, cfg: SparseAttentionConfig, *,
                     attention_mask: Optional[jax.Array] = None,
                     segment_ids: Optional[jax.Array] = None,
                     softmax_dtype=jnp.float32, compute_dtype=jnp.float32):
    """``q [b, T, nh, d]``, ``k`` / ``v`` ``[b, T, nkv, d]`` (rotated), the
    indexer's ``qi [b, T, Hi, di]``, ``ki [b, T, di]``, ``wi [b, T, Hi]`` ->
    ``(o [b, T, nh, d], stats)``: ``kl`` the mean over the real queries of
    ``KL_t``, ``kept_pairs`` / ``causal_pairs`` the selected and the visible
    pairs (float32 counts)."""
    b, t, nh, d = q.shape
    chunk = math.gcd(t, cfg.q_chunk)
    way = way_for(cfg, t, d, nh, k.shape[2], q.dtype)
    facts = shd.trace_facts()
    if facts is not None:
        # ``run_summary.json``: the way this trace took, and how often a layer
        # application forms ``p`` and the KL in a step: once where the loss's
        # gradient is taken in the forward (``_indexer_loss``), else in the
        # forward and again in the chunk's rerun
        facts["sparse_attention"] = {
            **cfg.facts(), "way": way,
            "loss_passes_per_layer_application": 1 if way == "flash_mask" else 2}
    real = (jnp.ones((b, t), bool) if attention_mask is None else attention_mask > 0)
    # the indexer's function, rerun in backward: the 16 heads' products of a
    # chunk are never kept
    scores_of = jax.checkpoint(functools.partial(index_scores, operand_dtype=compute_dtype))
    attend = jax.checkpoint(functools.partial(_attend_chunk, softmax_dtype=softmax_dtype))
    chunks: list[_Chunk] = []
    for r0 in range(0, t, chunk):
        rows, s = slice(r0, r0 + chunk), r0 + chunk
        visible = (jnp.arange(s)[None, :] <= jnp.arange(r0, s)[:, None])[None]
        visible = jnp.logical_and(visible, real[:, None, :s])
        visible = jnp.logical_and(visible, real[:, rows, None])
        if segment_ids is not None:
            visible = jnp.logical_and(
                visible, segment_ids[:, rows, None] == segment_ids[:, None, :s])
        with jax.named_scope("indexer"):
            scores = scores_of(qi[:, rows], ki[:, :s], wi[:, rows])
        if s > cfg.topk:
            with jax.named_scope("select"):
                sel = select(scores, visible, cfg)
        else:
            sel = visible
        chunks.append(_Chunk(rows, s, scores, sel,
                             jnp.sum(visible.astype(jnp.float32))))  # jaxlint: disable=JL106
    if way == "flash_mask":
        bq, bkv = _tiles(t, d, q.dtype, chunk)
        interpret = _interpret()
        masks = [c.sel.astype(jnp.int8) for c in chunks]
        mask = jnp.concatenate([jnp.pad(m, ((0, 0), (0, 0), (0, t - c.keys)))
                                for m, c in zip(masks, chunks)], axis=1)
        qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        o, lse = _per_shard(
            lambda *a: _flash_sel(*a, bq, bkv, interpret),
            (_ROWS4, _ROWS4, _ROWS4, _ROWS3), (_ROWS4, _ROWS3))(qt, kt, vt, mask)
        out = jnp.swapaxes(o, 1, 2)
        kl = _indexer_loss(qi, ki, wi, tuple(c.scores for c in chunks), tuple(masks), qt, kt,
                           lse, real, compute_dtype, interpret)
    else:
        outs, kls = [], []
        for c in chunks:
            o, kl = jax.lax.map(lambda a: attend(*a), (
                q[:, c.rows], k[:, :c.keys], v[:, :c.keys], c.scores, c.sel))
            outs.append(o)
            kls.append(kl)
        out = jnp.concatenate(outs, axis=1)
        kl = jnp.sum(jnp.where(real, jnp.concatenate(kls, axis=1), 0.0)) / _count(real)
    return out, {
        "kl": kl,
        "kept_pairs": sum(jnp.sum(c.sel.astype(jnp.float32)) for c in chunks),  # jaxlint: disable=JL106
        "causal_pairs": sum(c.shown for c in chunks)}
