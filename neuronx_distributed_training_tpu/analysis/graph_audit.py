"""Layer 1 of the pre-flight auditor: config→HLO contract checks.

``audit_config`` AOT-lowers the train step a YAML config describes — on
abstract inputs, with zero arrays materialized and no data files opened
(``trainer.loop.assemble_step_program(build_data=False)``) — and checks the
compiled artifact against the contracts the config declares:

- **GA001 donation**: every param/opt-state leaf the step donates must
  actually be aliased input→output in the compiled executable.  A "donated
  but copied" leaf silently doubles its resident bytes.
- **GA101/GA102 collective census**: the communication pattern GSPMD inserted
  must match the parallelism config — dp-only without ZeRO-1 has no business
  all-gathering anything; tp>1 without model-axis communication means the
  model silently replicated; dp>1 with no reduction means gradients never
  meet.
- **GA201 replication**: no intermediate tensor above an analytically derived
  per-device size budget (a replicated [b, s, vocab] logits block where a
  sharded one was intended is the classic silent OOM).
- **GA301 precision**: no f32×f32 matmuls in the traced program under a bf16
  compute regime (audited on the StableHLO, where dtypes are the program's
  own — backends may legitimately upcast later).

Each finding carries a rule ID, the offending HLO op, and a config-level
remediation hint (``docs/static_analysis.md`` is the catalogue).  Large
configs audit through ``shrink_overrides`` — dimensions shrink, parallel
degrees clamp to 2, but the *structure* (which axes exist, what is donated,
which collectives appear, which dtypes flow) is preserved.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
import re
from pathlib import Path
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.analysis.report import AuditReport

logger = logging.getLogger(__name__)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: HLO shape token: dtype[dims] — layout suffix excluded
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_ALIAS_PAIR_RE = re.compile(r"\{([0-9 ,]*)\}:\s*\(([0-9]+),")


# --------------------------------------------------------------------------
# abstract inputs
# --------------------------------------------------------------------------


def leaf_paths(tree: Any) -> list[str]:
    """Flatten-order leaf paths of a pytree — the names donation findings
    cite (flatten order matches XLA entry-parameter order for the leading
    donated arguments)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in flat]


def abstract_batch(asm: Any) -> dict[str, jax.ShapeDtypeStruct]:
    """The train step's batch as ShapeDtypeStructs, keyed by what the
    config's loss actually reads (pretrain/SFT vs preference alignment)."""
    cfg = asm.cfg
    gbs = int(asm.sched["global_batch_size"])
    seq = int((cfg.get("data", {}) or {}).get("seq_length")
              or getattr(asm.model_cfg, "max_position_embeddings", 0)
              or getattr(getattr(asm.model_cfg, "llama", None),
                         "max_position_embeddings", 0)
              or 2048)
    ids = jax.ShapeDtypeStruct((gbs, seq), jnp.int32)
    scalar = jax.ShapeDtypeStruct((gbs,), jnp.float32)
    if asm.alignment in ("dpo", "orpo"):
        batch = {"chosen_input_ids": ids, "rejected_input_ids": ids}
        if asm.alignment == "dpo":
            batch["reference_chosen_logps"] = scalar
            batch["reference_rejected_logps"] = scalar
        return batch
    if asm.alignment == "kto":
        batch = {
            "input_ids": ids,
            "kto_labels": jax.ShapeDtypeStruct((gbs,), jnp.int32),
            "reference_logps": scalar,
        }
        if str(asm.align_params.get("kl_estimator", "batch_mean")) == "mismatched":
            batch["kl_input_ids"] = ids
            batch["reference_kl_logps"] = scalar
        return batch
    return {"input_ids": ids, "labels": ids}


def abstract_opt_state(asm: Any) -> Any:
    """Abstract optimizer state tree via ``eval_shape`` over the same
    ``init_opt_state`` the trainer materializes with."""
    from neuronx_distributed_training_tpu.optim.adamw import init_opt_state

    return jax.eval_shape(
        functools.partial(
            init_opt_state, policy=asm.policy, ema=asm.ema_cfg is not None,
            health=asm.health_cfg.enabled,
            tensorstats=getattr(asm, "tensorstats_cfg", None),
            tensorstats_bucket_groups=tuple(
                getattr(asm, "tensorstats_bucket_groups", ())),
        ),
        asm.abstract_params,
    )


def lower_step_program(asm: Any):
    """AOT lower + compile the assembled step on abstract inputs, inside the
    mesh context (outside it every ``shd.constrain`` in the traced program
    silently no-ops — the graph would not be the one training runs).

    Returns ``(stablehlo_text, compiled)``."""
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    batch = abstract_batch(asm)
    opt = abstract_opt_state(asm)
    key = jax.random.PRNGKey(0)
    with asm.mesh, shd.use_mesh(asm.mesh):
        assert shd.active_mesh() is asm.mesh
        lowered = asm.jstep.lower(asm.abstract_params, opt, batch, key)
        compiled = lowered.compile()
    try:
        stablehlo = lowered.as_text()
    except Exception as e:  # noqa: BLE001 — dtype rule degrades, audit proceeds
        logger.warning("stablehlo text unavailable: %s", e)
        stablehlo = ""
    return stablehlo, compiled


# --------------------------------------------------------------------------
# the audit context: what the rules need, buildable from a StepProgram OR a
# live Trainer (the in-loop census audit)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class AuditContext:
    cfg: Any                 # the (possibly shrunk) ConfigDict
    mesh: Any
    policy: Any              # DtypePolicy
    model_cfg: Any
    sched: Mapping[str, int]
    donate: Any              # True/"all" | "params" | False
    params_tree: Any         # abstract or real pytree (shapes/paths only)
    opt_tree: Any
    pspecs: Any = None
    ospecs: Any = None

    @classmethod
    def from_step_program(cls, asm: Any) -> "AuditContext":
        return cls(
            cfg=asm.cfg, mesh=asm.mesh, policy=asm.policy,
            model_cfg=asm.model_cfg, sched=asm.sched, donate=asm.donate,
            params_tree=asm.abstract_params, opt_tree=abstract_opt_state(asm),
            pspecs=asm.pspecs, ospecs=asm.ospecs,
        )

    @property
    def ds(self) -> dict:
        return dict(self.cfg.get("distributed_strategy", {}) or {})

    @property
    def fusions(self) -> dict:
        return dict((self.cfg.get("model", {}) or {}).get("fusions", {}) or {})

    def axis(self, name: str) -> int:
        return int(self.mesh.shape.get(name, 1))


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------


def parse_alias_map(hlo_text: str) -> dict[int, int]:
    """``input_output_alias={ {3}: (17, {}, may-alias), ... }`` ->
    ``{output_flat_index: entry_param_number}``.  Nested output indices
    (``{1, 0}``) use the leading index — donated trees flatten to one level
    in practice.  The map nests braces (``{}`` param index paths), so the
    span is found by depth scan, not regex."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return {}
    i = hlo_text.index("{", start)
    depth, j = 0, i
    for j in range(i, min(len(hlo_text), i + 1_000_000)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo_text[i + 1: j]
    out: dict[int, int] = {}
    for om, pm in _ALIAS_PAIR_RE.findall(body):
        idx = [int(x) for x in om.replace(",", " ").split()]
        out[idx[0] if idx else 0] = int(pm)
    return out


def donation_map(ctx: AuditContext, hlo_texts: list[str]) -> dict[str, Any]:
    """Donation coverage accounting — the ONE implementation both GA001
    (``audit_donation``) and the graph-contract fingerprint (GC301,
    ``analysis.graph_contract``) read, so the absolute rule and the ratchet
    can never disagree about which leaves are donated or aliased.

    ``{"expected", "aliased", "coverage", "missing": [leaf paths]}`` —
    flatten order matches XLA entry-parameter order for the leading donated
    arguments."""
    donate = ctx.donate
    if donate in (False, "none", ()):
        return {"expected": 0, "aliased": 0, "coverage": 0.0, "missing": []}
    trees = [("params", ctx.params_tree)]
    if donate in (True, "all"):
        trees.append(("opt_state", ctx.opt_tree))
    paths: list[str] = []
    for name, tree in trees:
        paths.extend(f"{name}/{p}" for p in leaf_paths(tree))
    aliased: set[int] = set()
    for text in hlo_texts:
        aliased |= set(parse_alias_map(text).values())
    missing = [paths[i] for i in range(len(paths)) if i not in aliased]
    return {
        "expected": len(paths),
        "aliased": len(paths) - len(missing),
        "coverage": round(1.0 - len(missing) / max(len(paths), 1), 4),
        "missing": missing,
    }


def audit_donation(report: AuditReport, ctx: AuditContext,
                   hlo_texts: list[str]) -> None:
    """GA001: every donated param/opt leaf must be aliased input→output."""
    dm = donation_map(ctx, hlo_texts)
    if ctx.donate in (False, "none", ()):
        report.stats["donation_coverage"] = 0.0
        return
    report.stats["donated_expected"] = dm["expected"]
    report.stats["donated_aliased"] = dm["aliased"]
    report.stats["donation_coverage"] = dm["coverage"]
    for path in dm["missing"]:
        report.add(
            "GA001", "error",
            f"donated leaf {path}: its buffer is not reused by any "
            f"output in the compiled executable (donated-but-copied — the "
            f"bytes are resident twice)",
            location=f"donated leaf {path}",
            hint="a dtype/layout change between the input leaf and its "
                 "updated output defeats aliasing; keep the update "
                 "dtype-preserving (check DtypePolicy casts and optimizer "
                 "state dtypes)",
        )


def audit_collectives(report: AuditReport, ctx: AuditContext,
                      hlo_texts: list[str]) -> None:
    """GA101 (unexpected kind) / GA102 (missing kind) vs the parallelism
    config.  Count-level: the rules reason about which collective KINDS the
    config can explain, not their exact multiplicity."""
    from neuronx_distributed_training_tpu.utils.debug import (
        collective_counts_from_texts,
    )

    counts = collective_counts_from_texts(hlo_texts)
    report.stats["collectives"] = counts
    tp, pp, cp, ep = (ctx.axis("model"), ctx.axis("pipe"),
                      ctx.axis("context"), ctx.axis("expert"))
    dp = ctx.axis("data") * ep
    zero1 = bool(ctx.ds.get("zero1", True))
    seq_par = bool(ctx.ds.get("sequence_parallel", False))
    fus = ctx.fusions
    ulysses = bool(fus.get("ulysses_attention"))
    ring = bool(fus.get("ring_attention") or fus.get("zigzag_ring_attention"))
    moe = bool((ctx.cfg.get("model", {}) or {}).get("moe"))

    # -- unexpected kinds --------------------------------------------------
    # GSPMD legitimately reshards via all-to-all / collective-permute
    # whenever the sequence or expert dim changes owner mid-graph, so these
    # rules only bind in configs with NO sharded non-batch dim at all
    reshardy = (ep > 1 or cp > 1 or seq_par or moe
                or (ulysses and cp > 1))
    # ZeRO-1's shard/regather of updated params lowers partly as
    # collective-permute chains at higher dp degrees
    permutey = reshardy or (zero1 and dp > 1)
    if counts.get("all-to-all", 0) and not reshardy:
        report.add(
            "GA101", "warn",
            f"{counts['all-to-all']} all-to-all op(s) but no expert "
            f"parallelism, sequence/context sharding, or MoE configured to "
            f"explain them",
            location="all-to-all (HLO census)",
            hint="an unexplained all-to-all usually means GSPMD resolved a "
                 "sharding conflict by resharding; check PartitionSpecs at "
                 "the producer/consumer boundary",
        )
    if counts.get("collective-permute", 0) and pp == 1 and not permutey:
        report.add(
            "GA101", "warn",
            f"{counts['collective-permute']} collective-permute op(s) but "
            f"no pipeline stage transfers, ring attention, or "
            f"sequence/expert resharding is configured",
            location="collective-permute (HLO census)",
            hint="halo exchanges appear when a sharded dim is consumed with "
                 "a shifted index; check sequence-dim specs",
        )
    gather_kinds = counts.get("all-gather", 0) + counts.get("reduce-scatter", 0)
    if tp == 1 and cp == 1 and pp == 1 and ep == 1 and not seq_par:
        # dp-only: the only legal communication is gradient reduction —
        # plus the ZeRO-1 shard/regather pair when zero1 is on
        if gather_kinds and not zero1:
            report.add(
                "GA101", "error",
                f"dp-only config (zero1 off) has {counts.get('all-gather', 0)} "
                f"all-gather / {counts.get('reduce-scatter', 0)} "
                f"reduce-scatter op(s): something (likely full params or "
                f"optimizer state) is being regathered every step",
                location="all-gather/reduce-scatter (HLO census)",
                hint="a dp-only step should only all-reduce gradients; an "
                     "all-gather here means a param or activation was left "
                     "sharded/replicated inconsistently across the step "
                     "boundary (check param_specs vs opt_state_specs)",
            )
        if dp == 1 and any(counts.values()):
            report.add(
                "GA101", "warn",
                f"single-device program contains collectives: {counts}",
                location="HLO census",
                hint="collectives on a 1-device mesh are dead weight; check "
                     "for hand-rolled psum/shard_map over size-1 axes",
            )

    # -- missing kinds -----------------------------------------------------
    if tp > 1 and not any(counts.get(k, 0) for k in
                          ("all-reduce", "all-gather", "reduce-scatter")):
        report.add(
            "GA102", "error",
            f"tensor_model_parallel_size={tp} but the step has no model-axis "
            f"communication at all (no all-reduce/all-gather/reduce-scatter): "
            f"the model is either fully replicated or fully disconnected "
            f"across the model axis",
            location="HLO census",
            hint="check that param_specs actually name the 'model' axis and "
                 "that lowering happened inside the mesh context",
        )
    if dp > 1 and not any(counts.get(k, 0) for k in
                          ("all-reduce", "reduce-scatter")):
        report.add(
            "GA102", "error",
            f"data-parallel degree {dp} but no all-reduce or reduce-scatter "
            f"anywhere in the step: gradients are never reduced across "
            f"replicas",
            location="HLO census",
            hint="the loss must be a global mean over the dp-sharded batch; "
                 "check the batch PartitionSpec reaches the loss",
        )
    if dp > 1 and zero1 and not counts.get("all-gather", 0):
        report.add(
            "GA102", "warn",
            f"zero1 with dp={dp} but no all-gather in the step: updated "
            f"params are apparently not regathered from their optimizer "
            f"shards (or ZeRO-1 sharding never happened)",
            location="HLO census",
            hint="opt_state_specs should shard moments over (data, expert); "
                 "verify zero1 made it into opt_state_specs(zero1=...)",
        )
    if pp > 1 and not counts.get("collective-permute", 0):
        report.add(
            "GA102", "warn",
            f"pipeline_model_parallel_size={pp} but no collective-permute: "
            f"no inter-stage transfers were generated",
            location="HLO census",
            hint="the stage loop should shift activations over the 'pipe' "
                 "axis each tick; check the pipeline shard_map specs",
        )
    if seq_par and tp > 1 and not counts.get("all-gather", 0):
        # the reduce half may lower as all-reduce+slice rather than a
        # literal reduce-scatter op (backend-dependent), so only the gather
        # half is a hard expectation
        report.add(
            "GA102", "warn",
            f"sequence_parallel expects a pre-QKV all-gather over the model "
            f"axis; census has all-gather=0 (all-reduce="
            f"{counts.get('all-reduce', 0)})",
            location="HLO census",
            hint="activation specs between blocks should shard the seq dim "
                 "over 'model' (parallel.sharding.act_spec(sequence_parallel"
                 "=True))",
        )
    if moe and ep > 1 and not (counts.get("all-to-all", 0)
                               or counts.get("all-gather", 0)):
        report.add(
            "GA102", "warn",
            f"expert_model_parallel_size={ep} but no all-to-all/all-gather: "
            f"tokens are apparently never exchanged with their experts",
            location="HLO census",
            hint="expert specs should shard the expert dim over 'expert'; "
                 "check moe_param_specs reached the param tree",
        )


def _computation_blocks(hlo_text: str):
    """Yield ``(computation_name, [lines])`` — fusion bodies are separated so
    the replication rule can skip shapes that never materialize."""
    name, lines = "", []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and "{" in line:
            if lines:
                yield name, lines
            name, lines = line.split("(", 1)[0].strip(), []
        elif line.strip() == "}":
            if lines:
                yield name, lines
            name, lines = "", []
        else:
            lines.append(line)
    if lines:
        yield name, lines


def _shape_bytes(dtype: str, dims: str) -> int:
    n = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
    return n * _DTYPE_BYTES.get(dtype, 4)


def expected_max_device_bytes(ctx: AuditContext) -> int:
    """Analytic per-device budget: the largest tensor a CORRECTLY sharded
    step should materialize — max over sharded param/opt leaves, the local
    batch shard, and the known activation high-water candidates (ffn block,
    sharded logits, core-attention scores)."""
    mesh = ctx.mesh

    def sharded_leaf_bytes(tree, specs):
        best = 0
        if specs is None:
            return 0
        flat_t = jax.tree_util.tree_leaves(tree)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P) or x is None)
        for leaf, spec in zip(flat_t, flat_s):
            nbytes = int(math.prod(leaf.shape) * leaf.dtype.itemsize)
            denom = 1
            if isinstance(spec, P):
                for ax in spec:
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        if a is not None:
                            denom *= int(mesh.shape.get(a, 1))
            best = max(best, nbytes // max(denom, 1))
        return best

    candidates = [
        sharded_leaf_bytes(ctx.params_tree, ctx.pspecs),
        sharded_leaf_bytes(ctx.opt_tree, ctx.ospecs),
    ]

    mc = ctx.model_cfg
    lc = getattr(mc, "llama", mc)  # mixtral wraps a llama config
    tp, cp = ctx.axis("model"), ctx.axis("context")
    dp = ctx.axis("data") * ctx.axis("expert")
    seq = int((ctx.cfg.get("data", {}) or {}).get("seq_length")
              or getattr(lc, "max_position_embeddings", 2048))
    gbs = int(ctx.sched.get("global_batch_size", 1))
    mbs = int(ctx.sched.get("micro_batch_size", 1))
    b_local = max(gbs // max(dp, 1), mbs)
    # cotangents/accumulators run in grad_accum_dtype (f32 under mixed
    # precision), so activation candidates budget at the wider of the two
    abytes = max(jnp.dtype(ctx.policy.compute_dtype).itemsize,
                 jnp.dtype(getattr(ctx.policy, "grad_accum_dtype",
                                   jnp.float32)).itemsize)
    hidden = int(getattr(lc, "hidden_size", 0) or 0)
    ffn = int(getattr(lc, "intermediate_size", 0)
              or getattr(lc, "ffn_hidden_size", 0) or hidden)
    vocab = int(getattr(lc, "vocab_size", 0) or 0)
    heads = int(getattr(lc, "num_attention_heads", 1) or 1)
    n_layers = int(getattr(lc, "num_layers", 1) or 1)
    if hidden:
        # batch shard (int32 ids) and block-boundary / ffn activations
        candidates.append(b_local * seq * 4)
        candidates.append(b_local * seq * max(hidden, ffn) * abytes)
        # scan-over-layers remat saves a residual PER LAYER: the stacked
        # [L, b, s, h] carry is the activation-checkpoint high-water mark
        candidates.append(n_layers * b_local * seq * hidden * abytes)
        # lm-head logits, vocab sharded over model, f32 for the CE
        candidates.append(b_local * seq * max(vocab // max(tp, 1), 1) * 4)
        moe_cfg = getattr(mc, "moe", None)
        if moe_cfg is not None:
            # dropless routes [T*k] rows through the expert ffn
            k = int(getattr(moe_cfg, "top_k", 1) or 1)
            candidates.append(b_local * seq * k * max(ffn, hidden) * abytes)
        if getattr(lc, "attention_impl", "core") == "core":
            # naive scores materialize [b, heads/tp, s, s] in softmax dtype
            s_att = seq // max(cp, 1)
            candidates.append(
                b_local * max(heads // max(tp, 1), 1) * s_att * seq * 4)
    return max(candidates + [1])


def audit_replication(report: AuditReport, ctx: AuditContext,
                      hlo_texts: list[str], *, slack: float = 8.0,
                      max_findings: int = 8) -> None:
    """GA201: per-device tensors above ``slack``x the analytic budget.

    Post-SPMD HLO shapes are per-device, so an intermediate that dodged its
    PartitionSpec shows up ``axis_size``x larger than the budget — the rule
    catches replication factors above ``slack``.  Fusion bodies are skipped
    (their interior shapes never materialize)."""
    budget = expected_max_device_bytes(ctx)
    threshold = int(budget * slack)
    report.stats["replication_budget_bytes"] = budget
    report.stats["replication_threshold_bytes"] = threshold
    seen: set[str] = set()
    hits = 0
    for text in hlo_texts:
        for comp, lines in _computation_blocks(text):
            if "fused_computation" in comp:
                continue
            for line in lines:
                if "=" not in line:
                    continue
                lhs, _, rhs = line.partition("=")
                opname = lhs.strip()
                if opname in seen:
                    continue
                # first shape token after '=' is the op's output
                m = _SHAPE_RE.search(rhs.split("(")[0])
                if not m:
                    continue
                nbytes = _shape_bytes(m.group(1), m.group(2))
                if nbytes <= threshold:
                    continue
                # parameters are covered by the leaf budget; a parameter
                # larger than it means the leaf ISN'T sharded as specced,
                # which assert_tree_sharding owns — skip the noise here
                if " parameter(" in rhs:
                    continue
                seen.add(opname)
                hits += 1
                if hits <= max_findings:
                    report.add(
                        "GA201", "warn",
                        f"per-device intermediate {m.group(0)} is "
                        f"{nbytes / 1e6:.1f} MB — {nbytes / max(budget, 1):.1f}x "
                        f"the largest tensor a correctly-sharded step should "
                        f"hold ({budget / 1e6:.1f} MB)",
                        location=line.strip()[:160],
                        hint="an oversized intermediate usually means a "
                             "with_sharding_constraint was dropped (or "
                             "resolved to replicated); constrain the "
                             "producing activation's batch/seq dim",
                    )
    if hits > max_findings:
        report.add(
            "GA201", "info",
            f"{hits - max_findings} further oversized intermediates "
            f"suppressed (same probable root cause)",
        )


_STABLEHLO_DOT_RE = re.compile(
    r"stablehlo\.dot_general\s+(%[\w#]+),\s+(%[\w#]+)"
    r".*?:\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)"
)
_STABLEHLO_WIDEN_RE = re.compile(
    r"(%[\w#]+)\s*=\s*stablehlo\.convert\s.*?"
    r"\(tensor<[^>]*x(?:bf16|f16|f8\w*)>\)\s*->\s*tensor<[^>]*xf32>"
)


def audit_dtypes(report: AuditReport, ctx: AuditContext,
                 stablehlo_text: str, *, max_findings: int = 8) -> None:
    """GA301: f32×f32 matmuls in the traced program under a bf16 regime.

    Runs on StableHLO — the program as traced, before any backend-specific
    precision rewrites (CPU legitimately upcasts bf16 dots to f32 at the HLO
    level; that is not a config defect).  A dot whose f32 operand is a
    WIDENING convert from bf16 is the policy's own promotion (the f32
    softmax path meeting bf16 values — data is still bf16-precise) and is
    not flagged; the rule targets dots where both operands are genuinely
    f32-valued, i.e. the compute-dtype cast never happened.  No missed cast
    either: a sum written as a product with a table of ones, float32 on
    purpose (``ops/ssd.py``'s running sum of ``dt a`` inside a chunk,
    ``ops/norm.py``'s mean squares of a group).  It is known as the router's
    dot is, by an operand: one of the model config's ``sum_tables`` (the
    tables' shapes), multiplied at ``Precision.HIGHEST``."""
    if jnp.dtype(ctx.policy.compute_dtype) != jnp.dtype(jnp.bfloat16):
        return
    if not stablehlo_text:
        report.add(
            "GA301", "info",
            "StableHLO unavailable; f32-matmul check skipped",
        )
        return
    # the MoE router deliberately computes in f32 (routing decisions are
    # precision-sensitive); its dots are recognizable by the num_experts-
    # sized TRAILING dim one operand always carries ([h,E] fwd, [T,E] in
    # both transposes) — a genuine missed-cast matmul trails h/ffn/vocab
    moe_cfg = getattr(ctx.model_cfg, "moe", None) or (
        ctx.cfg.get("model", {}) or {}).get("moe")
    n_experts = int(getattr(moe_cfg, "num_experts", 0) or 0) if moe_cfg else 0

    def router_like(*type_strs: str) -> bool:
        if not n_experts:
            return False
        for t in type_strs:
            dims = [d for d in t.split("x")[:-1] if d.isdigit()]
            if dims and int(dims[-1]) == n_experts:
                return True
        return False

    sum_tables = getattr(ctx.model_cfg, "sum_tables", None)
    seq_len = int((ctx.cfg.get("data", {}) or {}).get("seq_length", 0) or 0)
    tables = {"x".join(map(str, t)) + "xf32"
              for t in (sum_tables(seq_len) if sum_tables else ())}

    def sum_as_product(line: str, *type_strs: str) -> bool:
        return ("precision = [HIGHEST, HIGHEST]" in line
                and any(t in tables for t in type_strs))

    hits = 0
    # MLIR SSA names (%N) are function-scoped: the widened-convert set is
    # rebuilt per func.func block so a convert in one function cannot
    # exempt an unrelated same-named dot operand in another
    for block in re.split(r"(?=^\s*func\.func\b)", stablehlo_text,
                          flags=re.M):
        widened = set(_STABLEHLO_WIDEN_RE.findall(block))
        for line in block.splitlines():
            m = _STABLEHLO_DOT_RE.search(line)
            if not m:
                continue
            lhs_name, rhs_name = m.group(1), m.group(2)
            e1 = m.group(3).rsplit("x", 1)[-1]
            e2 = m.group(4).rsplit("x", 1)[-1]
            if (e1 == "f32" and e2 == "f32"
                    and lhs_name not in widened and rhs_name not in widened
                    and not router_like(m.group(3), m.group(4))
                    and not sum_as_product(line, m.group(3), m.group(4))):
                hits += 1
                if hits <= max_findings:
                    report.add(
                        "GA301", "warn",
                        f"f32 x f32 matmul in a bf16 compute regime "
                        f"(tensor<{m.group(3)}> x tensor<{m.group(4)}>)",
                        location=line.strip()[:160],
                        hint="a dot whose BOTH operands are f32 under "
                             "precision.type mixed/bf16 bypasses the policy "
                             "cast — check the producing op applies "
                             "policy.compute_dtype",
                    )
    if hits > max_findings:
        report.add(
            "GA301", "info",
            f"{hits - max_findings} further f32 matmuls suppressed",
        )
    report.stats["f32_matmuls"] = hits


# --------------------------------------------------------------------------
# drivers
# --------------------------------------------------------------------------


def audit_artifacts(
    ctx: AuditContext,
    compiled: Any,
    stablehlo_text: str = "",
    *,
    replication_slack: float = 8.0,
    config_name: str = "",
) -> AuditReport:
    """Run every graph rule against an already-compiled executable.

    This is the shared core: the pre-flight CLI calls it on an abstract
    lowering, the trainer's compile census calls it on the very executable
    about to run."""
    from neuronx_distributed_training_tpu.telemetry.census import (
        hlo_texts_from_compiled,
    )

    from neuronx_distributed_training_tpu.telemetry.census import (
        memory_analysis_bytes,
    )

    report = AuditReport(config=config_name
                         or str(ctx.cfg.get("name", "") or ""))
    # XLA's own memory accounting rides every audit (the autotune planner
    # reads it back as the measured counterpart of its analytic HBM model;
    # arguments + temps is the resident figure — outputs alias donated args)
    mem = memory_analysis_bytes(compiled)
    if mem is not None:
        report.stats["memory_analysis"] = mem
        report.stats["memory_bytes"] = (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
        )
    try:
        hlo_texts = hlo_texts_from_compiled(compiled)
    except Exception as e:  # noqa: BLE001 — no HLO, no graph rules
        report.add(
            "GA000", "warn",
            f"compiled HLO unavailable ({type(e).__name__}: {e}); graph "
            f"rules skipped",
        )
        return report
    audit_donation(report, ctx, hlo_texts)
    audit_collectives(report, ctx, hlo_texts)
    audit_replication(report, ctx, hlo_texts, slack=replication_slack)
    audit_dtypes(report, ctx, stablehlo_text)
    return report


def audit_executable(ctx: AuditContext, compiled: Any, lowered: Any = None,
                     *, log=None, config_name: str = "") -> AuditReport:
    """One-call wrapper for callers holding a live ``(lowered, compiled)``
    pair (the trainer's in-loop census audit): the as_text fallback and
    finding logging live in one place."""
    stablehlo = ""
    if lowered is not None:
        try:
            stablehlo = lowered.as_text()
        except Exception as e:  # noqa: BLE001 — dtype rule degrades
            logger.debug("stablehlo text unavailable: %s", e)
    report = audit_artifacts(ctx, compiled, stablehlo,
                             config_name=config_name)
    if log is not None:
        for f in report.findings:
            log(f.format())
        log(f"graph audit: {report.worst() or 'clean'} (donation coverage "
            f"{100 * report.stats.get('donation_coverage', 0.0):.1f}%)")
    return report


def audit_step_program(asm: Any, *, replication_slack: float = 8.0,
                       config_name: str = "",
                       artifacts_out: Optional[dict] = None) -> AuditReport:
    """Lower + compile a :class:`StepProgram` abstractly and audit it.

    Spec lint (GA401) runs first: a spec naming an absent mesh axis (or
    double-using one) would die inside the partitioner with a message naming
    neither leaf nor axis — here it dies with both, and lowering is
    skipped.

    ``artifacts_out``, when given, receives ``{"ctx", "compiled",
    "stablehlo"}`` on a successful lowering — callers that ALSO fingerprint
    the artifact (the graph-contract ratchet riding a pre-flight sweep)
    reuse the one lowering instead of paying a second."""
    from neuronx_distributed_training_tpu.parallel.sharding import spec_errors

    errors = spec_errors({"params": asm.pspecs, "opt_state": asm.ospecs},
                         asm.mesh)
    if errors:
        report = AuditReport(config=config_name
                             or str(asm.cfg.get("name", "") or ""))
        for e in errors:
            report.add(
                "GA401", "error", f"invalid PartitionSpec: {e}",
                hint="fix the spec before lowering; axes must come from the "
                     "mesh and appear at most once per spec",
            )
        return report
    stablehlo, compiled = lower_step_program(asm)
    ctx = AuditContext.from_step_program(asm)
    if artifacts_out is not None:
        artifacts_out.update(ctx=ctx, compiled=compiled, stablehlo=stablehlo)
    return audit_artifacts(
        ctx, compiled, stablehlo, replication_slack=replication_slack,
        config_name=config_name,
    )


# --------------------------------------------------------------------------
# config shrinking: audit a 405B config in seconds, preserving structure
# --------------------------------------------------------------------------


def shrink_overrides(cfg: Mapping, *, max_devices: int = 8) -> dict[str, Any]:
    """Dotted-path overrides that shrink a resolved config to audit size.

    Parallel degrees clamp to 2 (any degree > 1 exercises the same contract
    structure: the axis exists, its collectives appear, its divisibility
    rules bind); model dims shrink to the smallest shapes satisfying the
    clamped degrees; batch shrinks to one microbatch per dp rank (pipeline
    configs keep ``pp`` microbatches so the stage loop is real).  Everything
    structural — which fusions are on, precision regime, zero1, alignment,
    MoE layout — is preserved."""
    ds = dict(cfg.get("distributed_strategy", {}) or {})
    model = dict(cfg.get("model", {}) or {})
    data = dict(cfg.get("data", {}) or {})
    fus = dict(model.get("fusions", {}) or {})

    def clamp(key, default=1):
        return min(int(ds.get(key) or default), 2)

    tp = clamp("tensor_model_parallel_size")
    pp = clamp("pipeline_model_parallel_size")
    cp = clamp("context_parallel_size")
    ep = clamp("expert_model_parallel_size")
    vp = clamp("virtual_pipeline_model_parallel_size")
    world = tp * pp * cp * ep
    if world > max_devices:
        raise ValueError(
            f"shrunk world {world} still exceeds max_devices={max_devices}"
        )
    data_mult = 2 if world * 2 <= max_devices else 1
    dp = data_mult * ep

    o: dict[str, Any] = {
        "distributed_strategy.tensor_model_parallel_size": tp,
        "distributed_strategy.pipeline_model_parallel_size": pp,
        "distributed_strategy.context_parallel_size": cp,
        "distributed_strategy.expert_model_parallel_size": ep,
        "distributed_strategy.virtual_pipeline_model_parallel_size": vp,
    }

    # heads/hidden: smallest GQA-shaped stack satisfying tp (weight splits)
    # and tp*cp (ulysses head budget)
    heads = 2 * tp * cp
    kv = tp * cp
    head_dim = 16
    o["model.num_attention_heads"] = heads
    for key in ("num_key_value_heads", "num_query_groups"):
        if key in model:
            o[f"model.{key}"] = kv
    o["model.hidden_size"] = heads * head_dim
    for key in ("intermediate_size", "ffn_hidden_size"):
        if key in model:
            o[f"model.{key}"] = 2 * heads * head_dim
    for key in ("kv_channels", "head_dim"):
        if key in model:
            o[f"model.{key}"] = head_dim
    # HF-spelled widths of a family whose layers differ (models/laguna.py):
    # expert and shared-expert widths, expert counts, and per-layer head
    # counts in the ratios the config states
    for key in ("moe_intermediate_size", "shared_expert_intermediate_size"):
        if model.get(key):
            o[f"model.{key}"] = 2 * heads * head_dim
    for key in ("num_experts", "n_routed_experts"):  # models/laguna.py, kanana.py
        if model.get(key):
            o[f"model.{key}"] = max(2 * ep, 4)
            if model.get("num_experts_per_tok"):
                o["model.num_experts_per_tok"] = min(int(model["num_experts_per_tok"]), 2)
    # latent attention's dims (models/kanana.py): rope on half a toy head
    for key, width in (("qk_nope_head_dim", head_dim), ("v_head_dim", head_dim),
                       ("qk_rope_head_dim", head_dim // 2), ("kv_lora_rank", 2 * head_dim)):
        if key in model:
            o[f"model.{key}"] = width
    per_layer = model.get("num_attention_heads_per_layer")
    if per_layer:
        counts = per_layer.values() if isinstance(per_layer, Mapping) else per_layer
        least = min(int(c) for c in counts)
        o["model.num_attention_heads_per_layer"] = (
            {t: heads * int(c) // least for t, c in per_layer.items()}
            if isinstance(per_layer, Mapping) else
            [heads * int(c) // least for c in per_layer])
    o["model.vocab_size"] = 128 * tp
    if "sliding_window" in model and model.get("sliding_window"):
        o["model.sliding_window"] = 32

    # layers: one whole (MoE + dense) group per stage chunk
    moe = dict(model.get("moe", {}) or {})
    moe_freq = int(model.get("moe_frequency", moe.get("moe_frequency", 1)) or 1)
    chunks = max(pp * vp, 1)
    o["model.num_layers"] = max(moe_freq, 1) * max(chunks, 2 // max(moe_freq, 1))
    if moe:
        o["model.moe.num_experts"] = max(2 * ep, 4)
        if moe.get("top_k"):
            o["model.moe.top_k"] = min(int(moe["top_k"]), 2)

    # sequence/batch: divisibility by cp (and 2*cp for zigzag) at seq 64;
    # flash/blockwise kv tiles shrink with it
    seq = 64 * max(cp, 1)
    o["data.seq_length"] = seq
    if "max_position_embeddings" in model:
        o["model.max_position_embeddings"] = seq
    if "encoder_seq_length" in model:
        o["model.encoder_seq_length"] = seq
    for key in ("flash_block_q", "flash_block_kv"):
        if fus:
            o[f"model.fusions.{key}"] = 16
    nm = pp if pp > 1 else 1
    o["data.micro_batch_size"] = 1
    o["data.global_batch_size"] = dp * nm
    return o


def audit_config(
    source: str | Path | Mapping,
    *,
    devices: Optional[list] = None,
    shrink: bool = True,
    max_devices: Optional[int] = None,
    replication_slack: float = 8.0,
    overrides: Optional[Mapping] = None,
    artifacts_out: Optional[dict] = None,
) -> AuditReport:
    """Load a YAML config, (optionally) shrink it, AOT-lower its train step,
    and audit the compiled artifact.  The one-call entry the CLI and the
    per-example-config test sweep use.

    Config-level validation failures become a GA000 error finding rather
    than an exception: the audit's job is a verdict, not a traceback."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    name = Path(source).name if isinstance(source, (str, Path)) else str(
        dict(source).get("name", "<mapping>"))
    report = AuditReport(config=name)
    try:
        cfg = load_config(source, overrides)
    except Exception as e:  # noqa: BLE001 — config errors ARE the verdict
        report.add(
            "GA000", "error",
            f"config failed validation: {type(e).__name__}: {e}",
            hint="fix the config; the loader's message names the knob",
        )
        return report
    devices = devices if devices is not None else jax.devices()
    # shrunk audits run on a CANONICAL world (≤ 8 devices) END TO END: both
    # the shrink itself (data_mult / global_batch_size) and the lowering
    # pool below — the compiled artifact, and the graph-contract fingerprint
    # snapshotted from it, must not depend on how many virtual devices this
    # machine's pool happens to hold
    avail = min(len(devices), 8) if shrink else len(devices)
    if max_devices is None:
        max_devices = avail
    try:
        if shrink:
            shr = shrink_overrides(cfg, max_devices=max_devices)
            if overrides:
                shr.update(overrides)
            cfg = load_config(source, shr) if isinstance(
                source, (str, Path)) else load_config(dict(source), shr)
            report.stats["shrunk"] = True
        asm = assemble_step_program(
            cfg, devices=list(devices)[: _world_of(cfg, avail)],
            build_data=False,
        )
    except Exception as e:  # noqa: BLE001 — assembly errors ARE the verdict
        report.add(
            "GA000", "error",
            f"train step assembly failed: {type(e).__name__}: {e}",
            hint="the config lowers no further than assembly; the message "
                 "names the failing subsystem",
        )
        return report
    sub = audit_step_program(
        asm, replication_slack=replication_slack, config_name=name,
        artifacts_out=artifacts_out)
    report.extend(sub)
    return report


def _world_of(cfg: Mapping, available: int) -> int:
    """Smallest device count the config's mesh accepts: the model axes exactly,
    times the largest data factor that fits ``available``."""
    ds = dict(cfg.get("distributed_strategy", {}) or {})
    base = 1
    for k in ("tensor_model_parallel_size", "pipeline_model_parallel_size",
              "context_parallel_size", "expert_model_parallel_size"):
        base *= int(ds.get(k) or 1)
    if base > available:
        raise ValueError(
            f"config needs at least {base} devices for its parallel degrees; "
            f"{available} available (raise "
            f"--xla_force_host_platform_device_count)"
        )
    world = base
    while world * 2 <= available:
        world *= 2
    # keep dp = world/base a power-of-two multiple but small: one doubling
    # is enough to surface data-axis collectives
    return min(world, base * 2)
