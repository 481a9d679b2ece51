"""Graph contracts: a compile-artifact regression ratchet with provenance.

The graph auditor (``analysis.graph_audit``) checks each compiled step
against *absolute* rules; nothing there catches *relative* drift — a
refactor can add an unplanned GSPMD reshard, drop a donated buffer, or
upcast a matmul and still pass every threshold.  This module makes the
compiled artifact itself a contract:

- ``fingerprint_artifacts`` extracts a **contract fingerprint** from a
  compiled train step: the collective census by kind × mesh-axis-group,
  per-collective **provenance** (each collective attributed to the declared
  source that explains it — tp/SP layer comms, ZeRO-1 RS+AG, pp hops, cp
  ring/ulysses, ep dispatch/weight-gather, MoE permutes — classified with
  the same ``utils.debug.AXIS_COLLECTIVE_KINDS`` table the autotune cost
  model prices and the trace analytics measure), the donation coverage map,
  ``memory_analysis()`` bytes, and the matmul dtype census.  A collective no
  declared source explains is a GSPMD-inserted reshard: the fingerprint
  records it unattributed, with the nearest named source op XLA's metadata
  points at.
- ``diff_fingerprint`` is the semantic differ: it explains a regression in
  config-level terms ("data-axis all-gather count 2→4: ZeRO-1 parameter
  all-gather duplicated; likely spec change in optim/zero1") rather than as
  an HLO text diff.
- Golden snapshots live under ``analysis/contracts/<config>.json``.  The
  ratchet only shrinks silently: an improvement (fewer collectives, tighter
  memory) updates without ceremony, growth refuses to commit without an
  in-file justification line, and unattributed collectives refuse to commit
  without an explicit waiver.

Surfaces: ``tools/graph_contract.py`` (CLI check/update over the example
configs), the trainer's in-loop ``telemetry.graph_audit`` verdict (the very
executable about to train gets its collectives attributed), and the verify
gate.  ``docs/static_analysis.md`` documents the workflow.
"""

from __future__ import annotations

import dataclasses
import json
import logging
from pathlib import Path
from typing import Any, Mapping, Optional

from neuronx_distributed_training_tpu.analysis.report import AuditReport

logger = logging.getLogger(__name__)

#: committed golden snapshots, one per example config
CONTRACTS_DIR = Path(__file__).resolve().parent / "contracts"

#: fingerprint schema version — bump on incompatible shape changes (the
#: differ refuses to compare across versions)
FINGERPRINT_VERSION = 1

#: memory growth/shrink beyond this fraction of the committed resident bytes
#: is a finding (10% absorbs scheduler jitter across minor XLA changes while
#: catching a lost donation or a replicated tensor long before +20%)
MEMORY_TOLERANCE = 0.10


class ContractError(RuntimeError):
    """A config could not be fingerprinted (load/assembly/lowering failed)."""


# --------------------------------------------------------------------------
# mesh-axis resolution: which axes a replica-group partition spans
# --------------------------------------------------------------------------


def _mesh_partitions(mesh: Any) -> dict[frozenset, tuple[str, ...]]:
    """Canonical replica-group partition -> the mesh-axis subset spanning it.

    For every non-empty subset S of the mesh's non-trivial axes, the
    partition groups device ids that agree on every axis NOT in S.  A
    compiled collective whose ``replica_groups`` equal one of these
    partitions communicates exactly over S."""
    import itertools

    import numpy as np

    axes = list(mesh.axis_names)
    shape = [int(mesh.shape[a]) for a in axes]
    ids = np.empty(shape, dtype=np.int64)
    for idx in np.ndindex(*shape):
        ids[idx] = int(mesh.devices[idx].id)
    nontrivial = [i for i, s in enumerate(shape) if s > 1]
    out: dict[frozenset, tuple[str, ...]] = {}
    for r in range(1, len(nontrivial) + 1):
        for combo in itertools.combinations(nontrivial, r):
            keep = [i for i in range(len(axes)) if i not in combo]
            groups: dict[tuple, list[int]] = {}
            for idx in np.ndindex(*shape):
                key = tuple(idx[i] for i in keep)
                groups.setdefault(key, []).append(int(ids[idx]))
            part = frozenset(frozenset(g) for g in groups.values())
            out.setdefault(part, tuple(axes[i] for i in combo))
    # iteration order (dicts preserve insertion) is smallest-subset-first:
    # the covering fallback in _axes_of_op picks the MINIMAL axis set
    return out


def _axes_of_op(entry: Mapping[str, Any], mesh: Any,
                partitions: dict[frozenset, tuple[str, ...]],
                coords: dict[int, dict[str, int]]) -> Optional[tuple[str, ...]]:
    """Mesh axes one parsed collective op communicates over.

    ``None`` means the group structure matched no axis subset (an irregular
    partition — reported unattributed with its raw groups)."""
    pairs = entry.get("pairs")
    if pairs:
        axes: set[str] = set()
        moved = False
        for s, t in pairs:
            if s == t:
                continue  # identity pair: the no-op edge of a ring shift
            moved = True
            cs, ct = coords.get(s), coords.get(t)
            if cs is None or ct is None:
                return None
            axes |= {a for a in cs if cs[a] != ct[a]}
        if not moved:
            return ()  # all self-sends: no communication
        order = list(mesh.axis_names)
        return tuple(sorted(axes, key=order.index)) if axes else None
    groups = entry.get("groups")
    if groups is None:
        # replica_groups={}: every device in one group
        return tuple(a for a in mesh.axis_names if int(mesh.shape[a]) > 1)
    part = frozenset(frozenset(g) for g in groups if len(g) > 1)
    if not part:
        return ()  # singleton groups: a degenerate no-comm collective
    full = frozenset(frozenset(g) for g in groups)
    exact = partitions.get(full) or partitions.get(part)
    if exact is not None:
        return exact
    # No axis subset partitions EXACTLY this way — GSPMD sometimes emits
    # sub-axis collectives (e.g. groups spanning half the data axis when a
    # tensor dim splits across a bigger axis).  Attribute to the MINIMAL
    # axis subset whose partition covers every group: traffic confined
    # within an axis's blocks is still that axis's communication.
    # (_mesh_partitions iterates smallest subsets first.)
    for cand, axes_tuple in partitions.items():
        if all(any(g <= block for block in cand) for g in part):
            return axes_tuple
    return None


def _device_coords(mesh: Any) -> dict[int, dict[str, int]]:
    import numpy as np

    axes = list(mesh.axis_names)
    shape = [int(mesh.shape[a]) for a in axes]
    out: dict[int, dict[str, int]] = {}
    for idx in np.ndindex(*shape):
        out[int(mesh.devices[idx].id)] = dict(zip(axes, idx))
    return out


# --------------------------------------------------------------------------
# declared sources: the provenance classes a config can explain
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeclaredComms:
    """What the config declares — the facts provenance classifies against.
    Derived identically to ``graph_audit.audit_collectives`` so the absolute
    rules and the ratchet can never disagree about a config's intent."""

    tp: int
    pp: int
    cp: int
    ep: int
    dp: int          # data axis only; the compound dp degree is dp * ep
    zero1: bool
    seq_par: bool
    moe: bool
    ulysses: bool
    ring: bool
    accum: bool = False  # gradient accumulation (num_microbatches > 1)
    zero1_bucket: bool = False  # engineered overlap: bucketed ZeRO-1 gathers
    moe_dropless: bool = False  # the sort + ragged_dot block (else capacity)

    @classmethod
    def from_ctx(cls, ctx: Any) -> "DeclaredComms":
        from neuronx_distributed_training_tpu.ops.moe import MoEConfig

        fus = ctx.fusions
        dp_total = ctx.axis("data") * ctx.axis("expert")
        gbs = int(ctx.sched.get("global_batch_size", 1) or 1)
        mbs = int(ctx.sched.get("micro_batch_size", 1) or 1)
        overlap = ctx.ds.get("overlap") or {}
        model = ctx.cfg.get("model", {}) or {}
        moe_block = model.get("moe")
        # the HF spellings of a routed block (models/laguna.py, kanana.py): always dropless
        hf_experts = bool(model.get("num_experts") or model.get("n_routed_experts"))
        return cls(
            tp=ctx.axis("model"), pp=ctx.axis("pipe"),
            cp=ctx.axis("context"), ep=ctx.axis("expert"),
            dp=ctx.axis("data"),
            zero1=bool(ctx.ds.get("zero1", True)),
            zero1_bucket=(bool(ctx.ds.get("zero1", True))
                          and float(overlap.get("zero1_bucket_mb", 0) or 0) > 0),
            seq_par=bool(ctx.ds.get("sequence_parallel", False)),
            moe=bool(moe_block) or hf_experts,
            moe_dropless=(MoEConfig.from_config(moe_block).dropless
                          if moe_block else hf_experts),
            ulysses=bool(fus.get("ulysses_attention")),
            ring=bool(fus.get("ring_attention")
                      or fus.get("zigzag_ring_attention")),
            accum=gbs > mbs * max(dp_total, 1),
        )

    @property
    def dp_total(self) -> int:
        return self.dp * self.ep


_DP_AXES = frozenset({"data", "expert"})
_BATCH_AXES = frozenset({"data", "expert", "context"})


def _src_any(*needles: str):
    """Source-op predicate: the metadata ``op_name`` of at least one op in
    the group mentions one of the needles (the corroborating evidence a
    sharper class demands)."""
    def pred(source_ops: list[str]) -> bool:
        return any(n in s for s in source_ops for n in needles)
    return pred


def declared_source_classes(d: DeclaredComms) -> list[tuple]:
    """Ordered ``(label, kinds, axes_predicate, src_predicate, grow_hint)``
    rules; the first rule matching a collective group's (kind, axis-set,
    source ops) names its source.  Kind sets come from
    ``utils.debug.AXIS_COLLECTIVE_KINDS`` — the same classes the autotune
    cost model prices per axis and the trace analytics measure, so all
    three surfaces agree on what each axis's traffic is.  ``src_predicate``
    (may be None) demands corroborating XLA ``op_name`` metadata — classes
    that would otherwise over-claim (embedding exchange, MoE routing) only
    match collectives whose nearest named op is the declared mechanism."""
    from neuronx_distributed_training_tpu.utils.debug import (
        AXIS_COLLECTIVE_KINDS as AK,
    )

    rules: list[tuple] = []

    def add(label, kinds, pred, hint, src=None):
        rules.append((label, tuple(kinds), pred, src, hint))

    if d.moe_dropless:
        # routing runs on the global tokens under GSPMD, ahead of the
        # per-shard expert block (sinkhorn normalises over all of them).  The
        # CPU partitioner these contracts lower for replicates top_k and
        # regathers the [tokens, E] float32 logits; the TPU compiler splits
        # it by rows (tests/test_tpu_compile.py).  The sort of the rows is
        # per shard: a "sort" gather is no declared cost of this path.  Ahead
        # of ZeRO-1's class, which claims any gather over the batch axes
        add("MoE router top-k gather", ("all-gather",),
            lambda a: bool(a),
            "the router's top-k regathers more than the logits; check "
            "ops/moe.py _dropless_on_mesh (routing ahead of the region)",
            src=_src_any("router/top_k"))
    if d.moe_dropless and d.ep > 1:
        # the expert exchange (ops/moe.py _exchange_experts), all of it over
        # 'expert'.  While no chip would receive more than its bound the rows
        # travel: dispatch all-gathers each peer's token shard (rows, gate
        # weights, choices), combine sends every peer its block of the
        # gate-weighted outputs and sums it there (all-to-all + local sum);
        # each is the other's transpose, so both kinds appear under both
        # scopes.  Past the bound (a branch only where a chip can receive
        # more than twice its share) the weights travel instead: each expert
        # weight all-gathered once, its float32 gradient reduce-scattered
        # once.  (The largest received row count is max-reduced over the
        # region's axes: one more all-reduce of the dp class.)  Ahead of the
        # classes whose source needles ("gather") or axis sets (ZeRO-1's)
        # would claim them.
        add("ep exchange all-gather", ("all-gather",),
            lambda a: a == {"expert"},
            "the expert exchange changed; ops/moe.py gathers each token "
            "shard (rows, gate weights, choices) over 'expert' under "
            "moe/dispatch in either pass and the outputs' cotangent under "
            "moe/combine, and past the row bound each expert weight once",
            src=_src_any("moe/shard_map/dispatch/", "moe/shard_map/combine/",
                         "experts/shard_map/all_gather"))
        add("ep exchange all-to-all", ("all-to-all",),
            lambda a: a == {"expert"},
            "the expert exchange changed; ops/moe.py returns each peer's "
            "block of expert outputs over 'expert' by one all-to-all per MoE "
            "layer under moe/combine, and the gathered rows' and gate "
            "weights' cotangents by one each under moe/dispatch",
            src=_src_any("moe/shard_map/dispatch/", "moe/shard_map/combine/"))
        add("ep expert gradient reduce-scatter", ("reduce-scatter",),
            lambda a: a == {"expert"},
            "the weights' way of the expert exchange changed; past the row "
            "bound ops/moe.py reduce-scatters each expert-weight gradient "
            "over 'expert' exactly once per MoE layer, in reduce_dtype",
            src=_src_any("experts/shard_map/reduce_scatter"))
    if d.tp > 1:
        add("tp/SP layer collective", AK["tp"],
            lambda a: a == {"model"},
            "tensor-parallel layer communication changed; check the layer "
            "PartitionSpecs (parallel/sharding act_spec/param_specs) and "
            "model.fusions")
        if d.seq_par:
            add("SP seq<->hidden reshard", ("all-to-all",),
                lambda a: a == {"model"},
                "sequence-parallel boundary moved; check act_spec("
                "sequence_parallel=True) placement between blocks")
            # slicing/padding a seq-dim-sharded activation (rotary shifts,
            # causal masks) consumes neighbours' rows: a halo exchange
            add("SP halo permute", ("collective-permute",),
                lambda a: a == {"model"},
                "a sequence-parallel activation is consumed at a shifted "
                "index (halo); check seq-dim slicing under SP",
                src=_src_any("slice", "pad", "concatenate", "roll"))
    if d.tp > 1:
        # the fused qkv / gate_up projection is column-sharded over model and
        # then split at head boundaries that need not fall on shard
        # boundaries: the partitioner moves the straddling columns with
        # neighbour permutes (jax 0.9 names the op ``split``)
        add("tp fused-projection split exchange", ("collective-permute",),
            lambda a: a == {"model"},
            "the split of a model-sharded fused projection moved; check "
            "fuse_qkv and the qkv/gate_up column PartitionSpecs",
            src=_src_any("split"))
    if d.tp > 1 or d.pp > 1:
        # vocab-parallel embedding: the token gather (and its scatter-add
        # transpose) crosses the model axis — composed with the batch axes,
        # and under pp additionally with the pipe axis (the embed/lm_head
        # stacks live on the edge stages)
        add("tp vocab/embedding exchange",
            ("collective-permute", "all-gather", "all-reduce"),
            lambda a: bool(a) and a <= (_BATCH_AXES | {"model", "pipe"}),
            "vocab-parallel embedding lookup traffic changed; check the "
            "embed/lm_head PartitionSpecs",
            src=_src_any("_take", "embed", "gather"))
    if d.dp_total > 1 or d.cp > 1:
        add("dp gradient/loss all-reduce", ("all-reduce",),
            lambda a: a and a <= _BATCH_AXES,
            "gradient/loss reduction over the batch axes changed; check "
            "that the loss stays a single global mean over the dp-sharded "
            "batch (trainer/step.py)")
    if d.zero1 and d.dp_total > 1:
        add("ZeRO-1 gradient reduce-scatter", ("reduce-scatter",),
            lambda a: a and a <= _DP_AXES,
            "ZeRO-1 gradient sharding changed; likely spec change in "
            "optim/zero1 (opt_state_specs)")
        if d.zero1_bucket:
            # engineered overlap (distributed_strategy.overlap.zero1_bucket_mb
            # > 0): the optimizer packs eligible leaves per layer-group bucket
            # and regathers each bucket with ONE combined all-gather under the
            # optim.overlap.BUCKET_AG_SCOPE named scope.  A named class so the
            # per-bucket collective-count growth is a justified fingerprint
            # change, not ZeRO-1 regather noise — ordered BEFORE the generic
            # rule; the scope corroboration keeps it from over-claiming.
            add("zero1-bucket combined all-gather", ("all-gather",),
                lambda a: a and a <= _DP_AXES,
                "bucketed ZeRO-1 regather changed; check distributed_"
                "strategy.overlap.zero1_bucket_mb and optim/overlap "
                "build_bucket_plan (one combined all-gather per bucket)",
                src=_src_any("zero1_bucket"))
        add("ZeRO-1 parameter all-gather", ("all-gather",),
            lambda a: a and a <= _DP_AXES,
            "ZeRO-1 resharding duplicated; likely spec change in optim/"
            "zero1 — updated params should regather exactly once per step")
        add("ZeRO-1 reshard permute", ("collective-permute",),
            lambda a: a and a <= _DP_AXES,
            "ZeRO-1 shard/regather permute chain changed; check "
            "opt_state_specs(zero1=...) against param_specs")
    if d.accum and d.dp_total > 1:
        # the grad-accumulation loop dynamic-slices microbatches out of the
        # dp-sharded global batch: re-tiling [gbs] rows from nm-per-device
        # to 1-per-device is an intra-data-axis exchange
        add("dp grad-accum microbatch reshard",
            ("all-to-all", "all-gather", "collective-permute"),
            lambda a: a and a <= _DP_AXES,
            "microbatch slicing across the dp-sharded batch changed; "
            "check the gradient-accumulation loop (trainer/step.py)")
    if d.pp > 1:
        add("pp stage hop", AK["pp"],
            lambda a: a == {"pipe"},
            "inter-stage transfer count changed; check the pipeline "
            "schedule's tick loop (parallel/pipeline.py)")
        # the stage loop psums partial losses/metrics across stages, and
        # shard_map boundaries regather stage-sharded values
        add("pp stage reduction", ("all-reduce",),
            lambda a: a == {"pipe"},
            "per-stage loss/metric reduction over the pipe axis changed; "
            "check the pipeline loss aggregation (parallel/pipeline.py)")
        # the stage body's manual-vjp psums (grads of values replicated
        # inside the shard_map) lower over the NON-pipe axes the body
        # replicates across
        add("pp stage-body grad reduction", ("all-reduce",),
            lambda a: bool(a) and "pipe" not in a,
            "the pipeline stage body's psum pattern changed; check the "
            "manual-vjp reductions in parallel/pipeline.py "
            "(pipeline_loss_and_grad)",
            src=_src_any("shmap_body"))
    if d.cp > 1:
        if d.ring:
            add("cp ring kv pass", ("collective-permute",),
                lambda a: a == {"context"},
                "ring-attention kv rotation changed; check parallel/"
                "ring_attention.py and the sequence-dim specs")
        if d.ulysses:
            add("cp ulysses head exchange", ("all-to-all",),
                lambda a: a == {"context"},
                "ulysses qkvo head exchange changed; check parallel/"
                "ulysses.py")
        add("cp sequence regather", ("all-gather",),
            lambda a: a == {"context"},
            "a sequence-sharded activation is being regathered over the "
            "context axis; check the seq-dim PartitionSpecs")
        # entering/leaving the CP fusion's shard_map regathers the
        # seq-sharded activation over the axes the body runs manual on
        add("cp shard_map boundary regather", ("all-gather",),
            lambda a: bool(a) and a <= {"context", "model"},
            "the CP fusion's shard_map boundary resharding changed; check "
            "the in/out specs of the ring/ulysses shard_map",
            src=_src_any("shard_map", "shmap"))
    if d.moe and not d.moe_dropless:
        if d.ep > 1:
            add("ep token all-to-all", AK["ep"],
                lambda a: "expert" in a and a <= (_DP_AXES | {"model"}),
                "expert token dispatch changed; check moe_param_specs and "
                "the routing path (ops/moe.py moe_dropped)")
        # capacity-factor routing ranks token assignments against the whole
        # batch (its capacity is a global cumsum): the top-k / cumsum /
        # one-hot workspace regathers across every sharded axis — declared
        # cost of dropped MoE (ops/moe.py), not a stray reshard
        add("MoE dropped routing gather", ("all-gather",),
            lambda a: bool(a),
            "dropped routing's top-k/cumsum workspace traffic changed; "
            "check the routing path (ops/moe.py moe_dropped)",
            src=_src_any("top_k", "sort", "argsort", "cumsum", "one_hot"))
    if d.moe:
        # dropped-mode dispatch/combine einsums contract the token dim
        # (sharded over batch axes and, under SP, the model axis): their
        # partial sums all-reduce over those axes; router aux losses reduce
        # the same way (the partitioner may instead all-gather the sharded
        # operand of the same einsum — one mechanism, either collective)
        add("MoE dispatch/combine reduction", ("all-reduce", "all-gather"),
            lambda a: bool(a) and a <= (_BATCH_AXES | {"model"}),
            "MoE dispatch/combine einsum or router-loss reduction changed; "
            "check ops/moe.py and the router aux-loss path",
            src=_src_any("dot_general", "reduce_sum", "einsum"))
        add("MoE permute", ("collective-permute", "all-to-all"),
            lambda a: a and "expert" in a,
            "MoE token permute pattern changed; check the routing path "
            "(ops/moe.py)")
    return rules


def attribute(kind: str, axes: Optional[tuple[str, ...]],
              source_ops: list[str],
              rules: list[tuple]) -> Optional[tuple[str, str]]:
    """``(source_label, grow_hint)`` of the first declared class explaining
    this collective group; ``None`` -> GSPMD-inserted, unattributed."""
    if axes is None:
        return None
    aset = set(axes)
    for label, kinds, pred, src, hint in rules:
        if kind not in kinds or not pred(aset):
            continue
        if src is not None and not src(source_ops):
            continue
        return label, hint
    return None


# --------------------------------------------------------------------------
# the fingerprint
# --------------------------------------------------------------------------


def _matmul_dtype_census(stablehlo_text: str) -> dict[str, Any]:
    """{``lhs_dtype x rhs_dtype``: count} over every ``dot_general`` in the
    traced program, plus one sample location per pair (what a dtype-upcast
    finding names)."""
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        _STABLEHLO_DOT_RE,
    )

    census: dict[str, int] = {}
    samples: dict[str, str] = {}
    for m in _STABLEHLO_DOT_RE.finditer(stablehlo_text):
        e1 = m.group(3).rsplit("x", 1)[-1]
        e2 = m.group(4).rsplit("x", 1)[-1]
        key = f"{e1}x{e2}"
        census[key] = census.get(key, 0) + 1
        samples.setdefault(
            key, f"dot_general (tensor<{m.group(3)}> x tensor<{m.group(4)}>)")
    return {"counts": dict(sorted(census.items())),
            "samples": dict(sorted(samples.items()))}


# donation accounting is shared with GA001: analysis.graph_audit.donation_map
# is the one implementation, so the absolute rule and this ratchet can never
# disagree about which leaves are donated or aliased


def fingerprint_artifacts(ctx: Any, compiled: Any, stablehlo_text: str = "",
                          *, config_name: str = "") -> dict[str, Any]:
    """Extract the contract fingerprint of a compiled train step.

    ``ctx`` is the same :class:`~.graph_audit.AuditContext` the absolute
    rules read; the fingerprint is pure host-side artifact inspection — no
    device work, no extra compiles — and is byte-stable across identical
    compiles (the snapshot tests pin this)."""
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        donation_map,
    )
    from neuronx_distributed_training_tpu.telemetry.census import (
        collective_ops_from_texts,
        hlo_texts_from_compiled,
        memory_analysis_bytes,
    )

    hlo_texts = hlo_texts_from_compiled(compiled)
    ops = collective_ops_from_texts(hlo_texts)
    partitions = _mesh_partitions(ctx.mesh)
    coords = _device_coords(ctx.mesh)
    rules = declared_source_classes(DeclaredComms.from_ctx(ctx))
    order = list(ctx.mesh.axis_names)

    # group by kind x axis-set first: attribution sees every group member's
    # source-op metadata (sharper classes demand corroborating evidence)
    grouped: dict[str, dict[str, Any]] = {}
    for entry in ops:
        axes = _axes_of_op(entry, ctx.mesh, partitions, coords)
        if axes == ():
            continue  # degenerate singleton-group op: no communication
        label = "+".join(axes) if axes is not None else "?"
        key = f"{entry['kind']}|{label}"
        g = grouped.setdefault(key, {"kind": entry["kind"], "axes": axes,
                                     "ops": [], "source_ops": []})
        g["ops"].append(entry["op"])
        if entry["source_op"]:
            g["source_ops"].append(entry["source_op"])

    collectives: dict[str, dict[str, Any]] = {}
    for key, g in grouped.items():
        src = attribute(g["kind"], g["axes"], g["source_ops"], rules)
        collectives[key] = {
            "count": len(g["ops"]),
            "source": src[0] if src else None,
            "hint": src[1] if src else "",
            "sample_ops": g["ops"][:2],
            "sample_source_ops": g["source_ops"][:2],
        }

    mem = memory_analysis_bytes(compiled) or {}
    memory = {k: int(mem[k]) for k in
              ("argument_size_in_bytes", "temp_size_in_bytes",
               "output_size_in_bytes") if k in mem}
    if memory:
        memory["resident_bytes"] = (
            memory.get("argument_size_in_bytes", 0)
            + memory.get("temp_size_in_bytes", 0))

    return {
        "version": FINGERPRINT_VERSION,
        "config": config_name or str(ctx.cfg.get("name", "") or ""),
        "mesh": {a: int(ctx.mesh.shape[a]) for a in order},
        "collectives": dict(sorted(collectives.items())),
        "donation": donation_map(ctx, hlo_texts),
        "matmul_dtypes": (_matmul_dtype_census(stablehlo_text)
                          if stablehlo_text else None),
        "memory": memory,
    }


def unattributed_entries(fp: Mapping[str, Any]) -> dict[str, dict[str, Any]]:
    return {k: v for k, v in (fp.get("collectives") or {}).items()
            if v.get("source") is None}


_GSPMD_HINT = (
    "an unattributed collective is a GSPMD-inserted reshard: the partitioner "
    "resolved a PartitionSpec conflict at this op's producer/consumer "
    "boundary by moving data; constrain the producing activation "
    "(shd.constrain) or declare the communication — or waive it explicitly "
    "with tools/graph_contract.py --update-contracts --justify"
)


def attribution_report(fp: Mapping[str, Any], *,
                       waivers: Mapping[str, str] | None = None,
                       config_name: str = "") -> AuditReport:
    """GC201 findings for every unattributed collective in a fingerprint —
    the provenance half of the contract, usable without a committed
    snapshot (the trainer's in-loop verdict)."""
    report = AuditReport(config=config_name or str(fp.get("config", "")))
    waivers = dict(waivers or {})
    unattributed = unattributed_entries(fp)
    report.stats["collectives_total"] = sum(
        v["count"] for v in (fp.get("collectives") or {}).values())
    report.stats["collectives_unattributed"] = sum(
        v["count"] for v in unattributed.values())
    for key, rec in sorted(unattributed.items()):
        if key in waivers:
            continue
        kind, _, axes = key.partition("|")
        near = rec.get("sample_source_ops") or rec.get("sample_ops") or []
        report.add(
            "GC201", "error",
            f"{rec['count']} {kind} op(s) over mesh axes [{axes}] have no "
            f"declared source in this config (GSPMD-inserted reshard); "
            f"nearest named op: {near[0] if near else '<unknown>'}",
            location=", ".join(rec.get("sample_ops", [])[:2]),
            hint=_GSPMD_HINT,
        )
    return report


# --------------------------------------------------------------------------
# the semantic differ
# --------------------------------------------------------------------------


def _explain_key(key: str) -> tuple[str, str]:
    kind, _, axes = key.partition("|")
    return kind, axes


def diff_fingerprint(old: Mapping[str, Any], new: Mapping[str, Any], *,
                     memory_tolerance: float = MEMORY_TOLERANCE,
                     waivers: Mapping[str, str] | None = None,
                     config_name: str = "") -> AuditReport:
    """Compare a fresh fingerprint against the committed contract.

    Error findings are regressions (the ratchet's fail condition); info
    findings are improvements the snapshot can tighten to.  Every message is
    config-level: it names the provenance class that regressed and the
    offending HLO ops, not an HLO text span."""
    report = AuditReport(config=config_name or str(new.get("config", "")))
    waivers = dict(waivers or {})

    if old.get("version") != new.get("version"):
        report.add(
            "GC002", "error",
            f"fingerprint version changed "
            f"{old.get('version')} -> {new.get('version')}: the committed "
            f"contract predates the current schema",
            hint="regenerate: tools/graph_contract.py --update-contracts",
        )
        return report
    if old.get("mesh") != new.get("mesh"):
        report.add(
            "GC002", "error",
            f"mesh changed {old.get('mesh')} -> {new.get('mesh')}: the "
            f"committed contract describes a different parallel layout",
            hint="a deliberate parallelism change must re-baseline: "
                 "tools/graph_contract.py --update-contracts --justify "
                 "'<why>'",
        )
        return report

    # -- collectives: per kind x axis-group counts + provenance ------------
    oc = dict(old.get("collectives") or {})
    nc = dict(new.get("collectives") or {})
    for key in sorted(set(oc) | set(nc)):
        a = int(oc.get(key, {}).get("count", 0))
        b = int(nc.get(key, {}).get("count", 0))
        kind, axes = _explain_key(key)
        rec = nc.get(key) or oc.get(key) or {}
        src = rec.get("source")
        if b > a:
            if src is None and key not in waivers:
                continue  # unattributed growth is GC201's finding below
            what = (f"{src} grew" if src
                    else f"waived reshard ({waivers.get(key, '')}) grew")
            near = rec.get("sample_ops", [])
            report.add(
                "GC101", "error",
                f"[{axes}]-axis {kind} count {a} -> {b}: {what} beyond the "
                f"committed contract"
                + (f" (e.g. {near[0]})" if near else ""),
                location=", ".join(near[:2]),
                hint=rec.get("hint") or
                "declare the change: tools/graph_contract.py "
                "--update-contracts --justify '<why the graph grew>'",
            )
        elif b < a:
            report.add(
                "GC110", "info",
                f"[{axes}]-axis {kind} count {a} -> {b}"
                f"{f' ({src})' if src else ''}: the graph got cheaper — "
                f"tighten the contract with --update-contracts",
            )

    # -- unattributed: every new-side reshard must be waived ---------------
    for key, rec in sorted(unattributed_entries(new).items()):
        if key in waivers:
            continue
        a = int(oc.get(key, {}).get("count", 0))
        b = int(rec.get("count", 0))
        kind, axes = _explain_key(key)
        near = rec.get("sample_source_ops") or rec.get("sample_ops") or []
        report.add(
            "GC201", "error",
            f"{b} {kind} op(s) over mesh axes [{axes}] have no declared "
            f"source (GSPMD-inserted reshard"
            + (f", count {a} -> {b}" if a else ", new")
            + f"); nearest named op: {near[0] if near else '<unknown>'}",
            location=", ".join(rec.get("sample_ops", [])[:2]),
            hint=_GSPMD_HINT,
        )

    # -- donation ----------------------------------------------------------
    od = dict(old.get("donation") or {})
    nd = dict(new.get("donation") or {})
    newly_missing = [p for p in nd.get("missing", [])
                     if p not in set(od.get("missing", []))]
    for path in newly_missing:
        report.add(
            "GC301", "error",
            f"donated leaf {path} lost its input->output alias (donation "
            f"regression: its bytes are now resident twice)",
            location=path,
            hint="a dtype/layout change between the input leaf and its "
                 "updated output defeats aliasing; keep the update "
                 "dtype-preserving (DtypePolicy casts, optimizer state "
                 "dtypes)",
        )
    if not newly_missing and float(nd.get("coverage", 0)) \
            < float(od.get("coverage", 0)):
        report.add(
            "GC301", "error",
            f"donation coverage fell {od.get('coverage')} -> "
            f"{nd.get('coverage')} "
            f"({nd.get('aliased')}/{nd.get('expected')} leaves aliased)",
            hint="the donated tree changed shape AND lost aliasing; "
                 "--update-contracts --justify after fixing or accepting it",
        )
    elif float(nd.get("coverage", 0)) > float(od.get("coverage", 0)):
        report.add(
            "GC110", "info",
            f"donation coverage improved {od.get('coverage')} -> "
            f"{nd.get('coverage')} — tighten with --update-contracts",
        )

    # -- matmul dtypes -----------------------------------------------------
    om = (old.get("matmul_dtypes") or {}).get("counts")
    nm = (new.get("matmul_dtypes") or {}).get("counts")
    if om is not None and nm is not None:
        samples = (new.get("matmul_dtypes") or {}).get("samples", {})
        for pair in sorted(set(om) | set(nm)):
            a, b = int(om.get(pair, 0)), int(nm.get(pair, 0))
            if b <= a:
                if b < a:
                    report.add(
                        "GC110", "info",
                        f"matmul dtype census {pair}: {a} -> {b}",
                    )
                continue
            # ANY growth of a wide-dtype pair is an upcast regression: an
            # upcast on a config that already carries legit f32 dots (the
            # router) shows up as count growth, not a new key, so both
            # forms must fail until declared.  Non-wide pair growth is
            # drift worth declaring but not a precision break (warn).
            widened = "f32" in pair or "f64" in pair
            report.add(
                "GC401", "error" if widened else "warn",
                f"matmul dtype census {pair}: {a} -> {b}"
                + (" — a matmul was upcast beyond the committed precision "
                   "regime" if widened and not a else ""),
                location=samples.get(pair, ""),
                hint="an upcast dot bypasses the compute-dtype policy "
                     "(the GA301 pitfall); check the producing op applies "
                     "policy.compute_dtype — or declare the change with "
                     "--update-contracts --justify" if widened else
                     "matmul count grew; declare the graph change with "
                     "--update-contracts --justify",
            )

    # -- memory ------------------------------------------------------------
    oldb = int((old.get("memory") or {}).get("resident_bytes", 0))
    newb = int((new.get("memory") or {}).get("resident_bytes", 0))
    if oldb and newb:
        ratio = newb / oldb - 1.0
        if ratio > memory_tolerance:
            report.add(
                "GC501", "error",
                f"compiled resident bytes grew {oldb} -> {newb} "
                f"(+{100 * ratio:.1f}% > {100 * memory_tolerance:.0f}% "
                f"tolerance)",
                hint="memory_analysis() argument+temp bytes regressed; the "
                     "usual causes are a lost donation (see GC301), a "
                     "dropped sharding constraint, or a remat policy "
                     "change — declare deliberate growth with "
                     "--update-contracts --justify",
            )
        elif ratio < -memory_tolerance:
            report.add(
                "GC110", "info",
                f"compiled resident bytes shrank {oldb} -> {newb} "
                f"({100 * ratio:.1f}%) — tighten with --update-contracts",
            )
    report.stats["memory_resident_bytes"] = newb
    return report


# --------------------------------------------------------------------------
# snapshots: load / check / update-with-justification
# --------------------------------------------------------------------------


def contract_path(config_name: str,
                  contracts_dir: Optional[Path] = None) -> Path:
    stem = Path(config_name).name
    for suffix in (".yaml", ".yml", ".json"):
        if stem.endswith(suffix):
            stem = stem[: -len(suffix)]
    return (contracts_dir or CONTRACTS_DIR) / f"{stem}.json"


def load_contract(config_name: str,
                  contracts_dir: Optional[Path] = None
                  ) -> Optional[dict[str, Any]]:
    path = contract_path(config_name, contracts_dir)
    if not path.exists():
        return None
    return json.loads(path.read_text())


def write_contract(config_name: str, fingerprint: Mapping[str, Any], *,
                   justifications: list[str],
                   waivers: Mapping[str, str] | None = None,
                   contracts_dir: Optional[Path] = None) -> Path:
    """Byte-stable snapshot write (sorted keys, fixed indent) — reruns with
    an identical artifact produce an identical file."""
    path = contract_path(config_name, contracts_dir)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "comment": "graph contract snapshot — regenerate with "
                   "tools/graph_contract.py --update-contracts; growth "
                   "must carry a --justify line (the ratchet only shrinks "
                   "silently)",
        "config": Path(config_name).name,
        "justifications": list(justifications),
        "waivers": dict(sorted((waivers or {}).items())),
        "fingerprint": fingerprint,
    }
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    return path


def check_contract(config_name: str, fingerprint: Mapping[str, Any], *,
                   memory_tolerance: float = MEMORY_TOLERANCE,
                   contracts_dir: Optional[Path] = None) -> AuditReport:
    """The ratchet's read side: diff a fresh fingerprint against the
    committed snapshot (plus the provenance check its waivers gate)."""
    name = Path(config_name).name
    snap = load_contract(config_name, contracts_dir)
    if snap is None:
        report = AuditReport(config=name)
        report.add(
            "GC000", "error",
            f"no committed contract for {name} "
            f"({contract_path(config_name, contracts_dir)})",
            hint="baseline it: tools/graph_contract.py --config <cfg> "
                 "--update-contracts",
        )
        return report
    report = diff_fingerprint(
        snap.get("fingerprint") or {}, fingerprint,
        memory_tolerance=memory_tolerance,
        waivers=snap.get("waivers") or {}, config_name=name,
    )
    report.stats["contract_path"] = str(
        contract_path(config_name, contracts_dir))
    return report


def update_contract(config_name: str, fingerprint: Mapping[str, Any], *,
                    justify: Optional[str] = None,
                    memory_tolerance: float = MEMORY_TOLERANCE,
                    contracts_dir: Optional[Path] = None
                    ) -> tuple[Path, AuditReport]:
    """The ratchet's write side.

    Shrinking (or identical) fingerprints commit silently, keeping existing
    justifications.  GROWTH — more collectives, lost donation, wider
    matmuls, more memory, or any unattributed collective — refuses to
    commit unless ``justify`` explains it; the justification is recorded
    in-file, and unattributed collectives become named waivers."""
    name = Path(config_name).name
    snap = load_contract(config_name, contracts_dir)
    old_just = list((snap or {}).get("justifications")
                    or ["initial contract baseline"])
    old_waivers = dict((snap or {}).get("waivers") or {})

    if snap is None:
        rep = AuditReport(config=name)
    else:
        rep = diff_fingerprint(
            snap.get("fingerprint") or {}, fingerprint,
            memory_tolerance=memory_tolerance, waivers=old_waivers,
            config_name=name,
        )
    unattributed = unattributed_entries(fingerprint)
    needs_justify = rep.failed("error") or any(
        k not in old_waivers for k in unattributed)
    if needs_justify and not justify:
        raise ContractError(
            f"{name}: the new fingerprint GROWS the contract "
            f"({', '.join(sorted({f.rule for f in rep.findings if f.severity == 'error'})) or 'unattributed collectives'}) "
            f"— growth must be declared: pass --justify '<why>' "
            f"(the ratchet only shrinks silently)\n{rep.format()}"
        )
    justifications = old_just + ([justify] if justify and (
        needs_justify or snap is None) else [])
    waivers = {k: v for k, v in old_waivers.items()
               if k in unattributed}  # stale waivers drop with the reshard
    for k in sorted(unattributed):
        waivers.setdefault(k, justify or old_waivers.get(k, ""))
    path = write_contract(config_name, fingerprint,
                          justifications=justifications, waivers=waivers,
                          contracts_dir=contracts_dir)
    return path, rep


# --------------------------------------------------------------------------
# config driver (the CLI / sweep entry)
# --------------------------------------------------------------------------


def fingerprint_config(
    source: str | Path | Mapping,
    *,
    devices: Optional[list] = None,
    shrink: bool = True,
    max_devices: Optional[int] = None,
    overrides: Optional[Mapping] = None,
) -> dict[str, Any]:
    """Load a YAML config, (optionally) shrink it with the graph auditor's
    ``shrink_overrides``, AOT-lower its train step on abstract inputs, and
    fingerprint the compiled artifact.  Raises :class:`ContractError` when
    the config cannot be lowered (the CLI turns that into a GC000 finding)."""
    import jax

    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        AuditContext,
        _world_of,
        lower_step_program,
        shrink_overrides,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    name = Path(source).name if isinstance(source, (str, Path)) else str(
        dict(source).get("name", "<mapping>"))
    devices = devices if devices is not None else jax.devices()
    # canonical ≤8-device world under shrink, END TO END: the shrink itself
    # (data_mult / global_batch_size) and the lowering pool — the
    # fingerprint (and the committed snapshot diffed against it) must not
    # depend on the size of this machine's virtual device pool
    avail = min(len(devices), 8) if shrink else len(devices)
    if max_devices is None:
        max_devices = avail
    try:
        cfg = load_config(source, overrides)
        if shrink:
            shr = shrink_overrides(cfg, max_devices=max_devices)
            if overrides:
                shr.update(overrides)
            cfg = load_config(source, shr) if isinstance(
                source, (str, Path)) else load_config(dict(source), shr)
        asm = assemble_step_program(
            cfg, devices=list(devices)[: _world_of(cfg, avail)],
            build_data=False,
        )
        stablehlo, compiled = lower_step_program(asm)
    except ContractError:
        raise
    except Exception as e:  # noqa: BLE001 — the CLI reports, not tracebacks
        raise ContractError(
            f"{name}: could not fingerprint: {type(e).__name__}: {e}"
        ) from e
    ctx = AuditContext.from_step_program(asm)
    fp = fingerprint_artifacts(ctx, compiled, stablehlo, config_name=name)
    fp["shrunk"] = bool(shrink)
    return fp
