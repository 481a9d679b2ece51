"""Engineered compute/comms overlap for the ZeRO-1 update.

The measurement plane (per-class achieved overlap from device traces) says
the step is bandwidth-bound at scale; this module is the *engineering* side:
it turns the monolithic step-boundary ZeRO-1 collectives into scheduled,
bucketed pieces the XLA latency-hiding scheduler can actually hide (cf.
DeepCompile's compiler-driven decomposition of ZeRO collectives, and the weight-update
sharding analysis in arXiv:2004.13336).

Three levers, all opt-in via ``distributed_strategy.overlap``:

- **Bucketed ZeRO-1 collectives** (``zero1_bucket_mb``): the AdamW update is
  decomposed into per-layer-group buckets (riding the health plane's
  ``grad_group_of`` naming).  Per bucket, every DP-sharded master/moment
  leaf's updated parameter is packed into ONE ``[dp, cols]`` buffer and
  resharded replicated in a single combined all-gather (``zero1_bucket_ag``
  named scope — the graph-contract ``zero1-bucket`` provenance class),
  instead of GSPMD's one all-gather per leaf at the step boundary.  Buckets
  are processed in reverse tree order — approximately gradient-completion
  order — so the first bucket's collective is in flight while later buckets
  are still computing.  The gradient reductions themselves are placed by
  GSPMD at their production sites (the backward); what bucketing controls
  is the *consumption* chain: each bucket's update can issue as soon as its
  group's grads are final instead of waiting for the whole tree.
- **Prefetched all-gathers** (``prefetch_ag``): an ``optimization_barrier``
  chain ties bucket k+1's gradient inputs to bucket k's pre-all-gather
  output.  Bucket k's all-gather and bucket k+1's update then depend on the
  same value but not on each other — the staggered structure the
  latency-hiding scheduler needs to overlap the gather with compute, and
  the prefetch that lands bucket k's replicated params ahead of their first
  forward consumer instead of serializing at the boundary.
- **Latency-hiding-scheduler knobs** (``xla_lhs``): the XLA flag set that
  makes the above actionable on TPU (async collectives + the LHS pass),
  merged into ``XLA_FLAGS`` with conflict detection instead of blind
  appending.

``pp_double_buffer`` is consumed by ``parallel.pipeline``: the stage-hop
collective-permutes move out of their compute ``cond``s to the tick
boundaries the work-compacted table's write->first-read intervals allow,
so a hop overlaps the neighbouring tick's compute.
"""

from __future__ import annotations

import dataclasses
import difflib
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from neuronx_distributed_training_tpu.parallel import sharding as shd

#: the one named scope the combined all-gather lives under — graph contracts
#: corroborate the ``zero1-bucket`` provenance class against this substring
BUCKET_AG_SCOPE = "zero1_bucket_ag"

_OVERLAP_KEYS = ("zero1_bucket_mb", "prefetch_ag", "pp_double_buffer",
                 "xla_lhs")


@dataclasses.dataclass(frozen=True)
class OverlapConfig:
    """Validated ``distributed_strategy.overlap`` block (all levers off by
    default — the engineered paths are opt-in and graph-changing)."""

    zero1_bucket_mb: float = 0.0  # 0 = monolithic; >0 = coalesce grad groups
                                  # until a bucket holds >= this many MiB of
                                  # fp32 master weights
    prefetch_ag: bool = True      # barrier-chain buckets (no-op when
                                  # zero1_bucket_mb == 0)
    pp_double_buffer: bool = False  # hoist pipeline stage-hop permutes out of
                                    # their compute conds
    xla_lhs: bool = False         # export the TPU latency-hiding flag set

    @classmethod
    def from_config(cls, block: Optional[dict]) -> "OverlapConfig":
        if block is None:
            return cls()
        if not isinstance(block, dict):
            raise ValueError(
                "distributed_strategy.overlap must be a mapping, got "
                f"{type(block).__name__}"
            )
        for k in block:
            if k not in _OVERLAP_KEYS:
                near = difflib.get_close_matches(str(k), _OVERLAP_KEYS, n=1)
                hint = f" — did you mean '{near[0]}'?" if near else ""
                raise ValueError(
                    f"unknown distributed_strategy.overlap key '{k}'{hint} "
                    f"(valid: {', '.join(_OVERLAP_KEYS)})"
                )
        mb = block.get("zero1_bucket_mb", 0.0)
        if isinstance(mb, bool) or not isinstance(mb, (int, float)):
            raise ValueError(
                "distributed_strategy.overlap.zero1_bucket_mb must be a "
                f"number (MiB), got {type(mb).__name__}"
            )
        if mb < 0:
            raise ValueError(
                "distributed_strategy.overlap.zero1_bucket_mb must be >= 0, "
                f"got {mb}"
            )
        out = {"zero1_bucket_mb": float(mb)}
        for k in ("prefetch_ag", "pp_double_buffer", "xla_lhs"):
            if k in block:
                v = block[k]
                if not isinstance(v, bool):
                    raise ValueError(
                        f"distributed_strategy.overlap.{k} must be a bool, "
                        f"got {type(v).__name__}"
                    )
                out[k] = v
        return cls(**out)


# ---------------------------------------------------------------------------
# Bucket planning (static — built from abstract shapes + specs at assembly)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AGLeaf:
    """One leaf eligible for the combined all-gather: its moments/master are
    DP-sharded on exactly ``dim`` and the param spec is fully replicated, so
    the updated parameter can be packed shard-contiguously into the bucket's
    ``[dp, cols]`` buffer."""

    pos: int            # index into the flattened params tree
    dim: int            # the DP-sharded dim of the moment spec
    cols: int           # leaf.size // dp_total
    moved_shape: tuple  # shape after moveaxis(dim -> 0)


@dataclasses.dataclass(frozen=True)
class Bucket:
    name: str                  # "+".join of member grad groups
    idxs: tuple                # flattened leaf indices (all members)
    ag: tuple                  # AGLeaf entries (combined-gather members)
    bytes: int                 # fp32 master bytes in this bucket


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    buckets: tuple             # processing order: reverse tree-group order
    dp_entry: Any              # spec entry for the sharded pack dim
    dp_total: int
    num_leaves: int

    def describe(self) -> str:
        parts = [
            f"{b.name}[{len(b.idxs)} leaves, {len(b.ag)} packed, "
            f"{b.bytes / 2**20:.1f}MiB]"
            for b in self.buckets
        ]
        return f"zero1 buckets (dp={self.dp_total}): " + ", ".join(parts)


def _dp_avail(spec: P, mesh: Mesh, dp_axes) -> tuple:
    used = {
        a
        for e in spec
        if e is not None
        for a in (e if isinstance(e, tuple) else (e,))
    }
    return tuple(
        a for a in dp_axes if int(mesh.shape.get(a, 1)) > 1 and a not in used
    )


def _nontrivial_axes(entry: Any, mesh: Mesh) -> tuple:
    """The axes of one spec entry that actually shard on this mesh.  Specs
    routinely carry size-1 axis names ("model" on a dp-only mesh, "expert"
    on a dense run) — those partition nothing, and eligibility must judge
    the PHYSICAL layout, not the spelling."""
    if entry is None:
        return ()
    axes = entry if isinstance(entry, tuple) else (entry,)
    return tuple(a for a in axes if int(mesh.shape.get(a, 1)) > 1)


def build_bucket_plan(
    abstract_params,
    param_specs,
    moment_specs,
    mesh: Mesh,
    *,
    bucket_mb: float,
    group_fn: Callable,
    dp_axes=("data", "expert"),
) -> Optional[BucketPlan]:
    """Group the param tree's leaves into collective buckets.

    Leaves are grouped by ``group_fn(path)`` (the health plane's
    ``grad_group_of``), groups keep tree order, and consecutive groups are
    coalesced until a bucket holds ``bucket_mb`` MiB of fp32 master weights
    — so a tiny ``bucket_mb`` gives one bucket per group and a huge one
    gives a single bucket.  The returned processing order is REVERSED
    (approximately the backward's gradient-completion order).

    A leaf joins its bucket's combined all-gather only when the packing is
    provably a local reshape: the moment spec shards exactly one dim over
    the full available DP extent and the param spec is physically
    replicated (judged on mesh extents — size-1 axis names like "model" on
    a dp-only mesh don't disqualify; genuinely tp/ep-sharded params fall
    back to GSPMD's per-leaf gather, which keeps bucketing legal on any
    mesh).  Returns None when no DP extent is available (dp_total == 1) —
    bucketing is a no-op there.
    """
    leaves = jax.tree_util.tree_flatten_with_path(abstract_params)[0]
    treedef = jax.tree_util.tree_structure(abstract_params)
    pspecs = treedef.flatten_up_to(param_specs)
    mspecs = treedef.flatten_up_to(moment_specs)

    dp_total = 1
    for a in dp_axes:
        dp_total *= int(mesh.shape.get(a, 1))
    if dp_total == 1:
        return None

    # group leaves in tree order
    order: list = []
    members: dict = {}
    for pos, (path, leaf) in enumerate(leaves):
        g = group_fn(path)
        if g not in members:
            members[g] = []
            order.append(g)
        members[g].append(pos)

    dp_entry = None

    def ag_leaf(pos) -> Optional[AGLeaf]:
        nonlocal dp_entry
        leaf = leaves[pos][1]
        pspec, mspec = pspecs[pos], mspecs[pos]
        if tuple(mspec) == tuple(pspec):
            return None  # not ZeRO-1 sharded (excluded / nothing divides)
        if any(_nontrivial_axes(e, mesh) for e in pspec):
            return None  # param itself model-sharded: per-leaf fallback
        avail = _dp_avail(pspec, mesh, dp_axes)
        entry = avail if len(avail) > 1 else (avail[0] if avail else None)
        if entry is None:
            return None
        sharded = [
            (i, _nontrivial_axes(e, mesh))
            for i, e in enumerate(mspec)
            if _nontrivial_axes(e, mesh)
        ]
        if len(sharded) != 1 or sharded[0][1] != tuple(
                entry if isinstance(entry, tuple) else (entry,)):
            return None
        dim = sharded[0][0]
        shape = tuple(leaf.shape)
        if dim >= len(shape) or shape[dim] % dp_total != 0:
            return None
        size = 1
        for d in shape:
            size *= d
        if size == 0:
            return None
        if dp_entry is None:
            dp_entry = entry
        elif dp_entry != entry:
            return None  # mixed extents: keep the pack uniform
        moved = (shape[dim],) + shape[:dim] + shape[dim + 1:]
        return AGLeaf(pos=pos, dim=dim, cols=size // dp_total,
                      moved_shape=moved)

    threshold = float(bucket_mb) * 2**20
    buckets: list = []
    cur_names: list = []
    cur_idxs: list = []
    cur_ag: list = []
    cur_bytes = 0

    def close():
        nonlocal cur_names, cur_idxs, cur_ag, cur_bytes
        if cur_idxs:
            buckets.append(Bucket(
                name="+".join(cur_names), idxs=tuple(cur_idxs),
                ag=tuple(cur_ag), bytes=cur_bytes,
            ))
        cur_names, cur_idxs, cur_ag, cur_bytes = [], [], [], 0

    for g in reversed(order):
        cur_names.append(g)
        for pos in members[g]:
            cur_idxs.append(pos)
            a = ag_leaf(pos)
            if a is not None:
                cur_ag.append(a)
            leaf = leaves[pos][1]
            size = 1
            for d in leaf.shape:
                size *= d
            cur_bytes += size * 4  # fp32 master
        if cur_bytes >= threshold:
            close()
    close()

    return BucketPlan(
        buckets=tuple(buckets),
        dp_entry=dp_entry,
        dp_total=dp_total,
        num_leaves=len(leaves),
    )


# ---------------------------------------------------------------------------
# The bucketed update (traced — called from optim.adamw.adamw_update)
# ---------------------------------------------------------------------------


def bucketed_update(
    plan: BucketPlan,
    params,
    grads,
    mu,
    nu,
    master,
    masks,
    *,
    mu_fn: Callable,
    nu_fn: Callable,
    upd_fn: Callable,
    prefetch: bool = True,
    collect_packed=None,
):
    """Per-bucket AdamW inner update with combined parameter all-gathers.

    Applies the SAME per-leaf lambdas the monolithic path uses (``mu_fn``,
    ``nu_fn``, ``upd_fn``) bucket by bucket, so the numerics are bitwise
    identical — only the collective structure changes.  For each bucket the
    eligible updated params are cast to param dtype, packed shard-contiguous
    into one ``[dp, cols]`` buffer, and resharded replicated under the
    ``zero1_bucket_ag`` scope: one all-gather per bucket instead of one per
    leaf.  With ``prefetch`` an ``optimization_barrier`` ties bucket k+1's
    grads to bucket k's pre-gather output, staggering the chain so gather k
    overlaps update k+1.

    ``collect_packed`` (a dict, or None): when given, each bucket's packed
    pre-gather ``[dp, cols]`` buffer is recorded under its bucket name — the
    tensor numerics observatory (``telemetry.tensorstats``) reads the exact
    payload the combined all-gather moves.  Purely observational: the traced
    update itself is unchanged.

    Returns ``(new_mu, new_nu, new_master, new_params)`` as trees.
    """
    treedef = jax.tree_util.tree_structure(params)
    p_l = treedef.flatten_up_to(params)
    g_l = treedef.flatten_up_to(grads)
    mu_l = treedef.flatten_up_to(mu)
    nu_l = treedef.flatten_up_to(nu)
    m_l = treedef.flatten_up_to(master)
    w_l = treedef.flatten_up_to(masks)

    n = plan.num_leaves
    out_mu = [None] * n
    out_nu = [None] * n
    out_master = [None] * n
    out_params = [None] * n
    token = None

    for bucket in plan.buckets:
        gb = [g_l[i] for i in bucket.idxs]
        if prefetch and token is not None:
            # stagger: this bucket's inputs wait on the previous bucket's
            # (pre-gather) output, so the previous gather is free to overlap
            # this bucket's compute
            chained = jax.lax.optimization_barrier(tuple(gb) + (token,))
            gb = list(chained[:-1])
        for j, i in enumerate(bucket.idxs):
            g = gb[j]
            nmu = mu_fn(mu_l[i], g)
            nnu = nu_fn(nu_l[i], g)
            nm = upd_fn(m_l[i], nmu, nnu, w_l[i])
            out_mu[i] = nmu
            out_nu[i] = nnu
            out_master[i] = nm
            out_params[i] = nm.astype(p_l[i].dtype)

        if bucket.ag:
            pieces = [
                jnp.moveaxis(out_params[a.pos], a.dim, 0).reshape(
                    plan.dp_total, a.cols)
                for a in bucket.ag
            ]
            packed = (jnp.concatenate(pieces, axis=1) if len(pieces) > 1
                      else pieces[0])
            packed = shd.constrain(packed, P(plan.dp_entry))
            if collect_packed is not None:
                collect_packed[bucket.name] = packed
            with jax.named_scope(BUCKET_AG_SCOPE):
                gathered = shd.constrain(packed, P())
                # the barrier pins the combined gather: without it XLA's
                # slice-through-all-gather rewrite commutes the unpack slices
                # into the gather and splits it back into per-leaf collectives
                gathered = jax.lax.optimization_barrier(gathered)
            off = 0
            for a in bucket.ag:
                piece = jax.lax.slice_in_dim(gathered, off, off + a.cols,
                                             axis=1)
                off += a.cols
                v = piece.reshape(a.moved_shape)
                out_params[a.pos] = jnp.moveaxis(v, 0, a.dim)
            token = packed
        else:
            token = out_mu[bucket.idxs[-1]]

    unflat = jax.tree_util.tree_unflatten
    return (unflat(treedef, out_mu), unflat(treedef, out_nu),
            unflat(treedef, out_master), unflat(treedef, out_params))


# ---------------------------------------------------------------------------
# XLA latency-hiding-scheduler knobs + XLA_FLAGS merging
# ---------------------------------------------------------------------------

#: the TPU flag set ``xla_lhs: true`` exports — async collectives plus the
#: latency-hiding scheduler pass that consumes the bucketed structure.
#: TPU-only spellings: unknown flags are FATAL to the CPU jaxlib's flag
#: parser, so callers must gate on the backend (see ``xla_lhs_flags``).
TPU_LHS_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)


def xla_lhs_flags(platform: str) -> tuple:
    """The flag set for ``xla_lhs: true`` on ``platform`` ("tpu"/"cpu"/...).

    Only TPU has the latency-hiding scheduler surface; every other backend
    returns empty (the knob is then an explicit no-op the caller should log,
    NOT an error — the same config must run on the CPU smoke)."""
    if str(platform).lower() == "tpu":
        return TPU_LHS_FLAGS
    return ()


def _flag_name(tok: str) -> str:
    return tok.split("=", 1)[0]


def merge_xla_flags(base: str, extra: Iterable[str]) -> tuple:
    """Merge ``extra`` flag tokens into an existing ``XLA_FLAGS`` string.

    User-provided flags WIN: an ``extra`` token whose flag name already
    appears in ``base`` with a different value is dropped and reported in
    ``conflicts`` (the caller warns).  Identical duplicates are dropped
    silently.  Returns ``(merged, conflicts)`` where ``conflicts`` is a list
    of ``(flag_name, base_token, extra_token)`` tuples.  This replaces the
    blind append whose duplicate-flag last-wins behavior was silent.
    """
    base_toks = [t for t in str(base or "").split() if t]
    by_name = {_flag_name(t): t for t in base_toks}
    merged = list(base_toks)
    conflicts = []
    for tok in extra:
        name = _flag_name(tok)
        cur = by_name.get(name)
        if cur is None:
            merged.append(tok)
            by_name[name] = tok
        elif cur != tok:
            conflicts.append((name, cur, tok))
    return " ".join(merged), conflicts
