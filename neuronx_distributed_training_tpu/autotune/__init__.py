"""Autotune — the compile-time parallelism & remat planner.

Given a model config and a chip count, the planner:

1. enumerates the legal plan lattice (``space``): factorizations of the world
   into dp x tp x pp x cp x ep respecting every divisibility rule the runtime
   enforces, microbatch counts compatible with the global batch, remat policy,
   and pipeline schedule (honoring the ``supports_1f1b`` gate) — all pruned
   statically, before any lowering;
2. scores each plan with an analytic roofline (``cost_model``): compute time
   from the per-component FLOPs breakdown, comms time from per-collective
   byte volumes mapped onto an ICI bandwidth/latency table (``topology``),
   pipeline bubble from the schedule, and a per-device HBM estimate;
3. AOT-lowers the top-k shrunk (``planner``, reusing the graph auditor's
   ``shrink_overrides``) to replace estimates with measured
   ``memory_analysis()`` bytes and the real collective census, discards plans
   that fail the audit, and emits a :class:`PlanReport`.

Surfaces: ``tools/plan.py`` CLI and ``nxdt-train --autotune``.  Nothing
scores the cost model's ranking against a chip yet (``ROADMAP.md`` C1).
``docs/autotuning.md`` is the manual.
"""

from neuronx_distributed_training_tpu.autotune.cost_model import (  # noqa: F401
    PlanEstimate,
    estimate_hbm_bytes,
    estimate_plan,
    overlap_from_trace_summary,
    resolve_overlap,
)
from neuronx_distributed_training_tpu.autotune.planner import (  # noqa: F401
    PlanCandidate,
    PlanReport,
    plan_config,
    rank_plans,
)
from neuronx_distributed_training_tpu.autotune.space import (  # noqa: F401
    ModelFacts,
    Plan,
    enumerate_plans,
)
from neuronx_distributed_training_tpu.autotune.topology import (  # noqa: F401
    TOPOLOGIES,
    ChipTopology,
    resolve_topology,
)
