"""Unified step telemetry: span timing, MFU, compile census, goodput.

The observable surface the reference ships piecemeal (NeMo ``TimingCallback``,
``llama_perf_estimate.py``, profiler hooks) as ONE subsystem the trainer
threads through every sink: per-step span decomposition (``spans``), a
first-compile memory/collective/FLOPs census persisted to ``run_summary.json``
(``census``), retrace detection (``recompile``), the numerics flight recorder
(in-graph health probes in ``health``, ring buffer / anomaly bundles / hang
watchdog in ``flight_recorder``), and the ``exp_manager: telemetry:`` knob
block that gates it all (``config``).  Everything here is host-side
bookkeeping — no device syncs between logging boundaries (the anomaly dump
path, which only runs once a step has already gone non-finite, is the one
deliberate exception).
"""

from neuronx_distributed_training_tpu.telemetry.alerts import (
    ALERT_ACTIONS,
    AlertEngine,
    AlertRule,
    parse_alerts,
)
from neuronx_distributed_training_tpu.telemetry.census import (
    compile_census,
    memory_analysis_bytes,
)
from neuronx_distributed_training_tpu.telemetry.fleet import (
    FleetAggregator,
    FleetBeacon,
    FleetConfig,
    FleetPlane,
    aggregate_fleet,
)
from neuronx_distributed_training_tpu.telemetry.config import (
    TELEMETRY_KNOBS,
    TelemetryConfig,
)
from neuronx_distributed_training_tpu.telemetry.flight_recorder import (
    HangWatchdog,
    HealthMonitor,
)
from neuronx_distributed_training_tpu.telemetry.health import (
    HEALTH_POLICIES,
    HealthConfig,
    grad_group_of,
)
from neuronx_distributed_training_tpu.telemetry.memory import (
    MEMORY_SUMMARY_NAME,
    SUBSYSTEMS,
    MemoryConfig,
    MemoryPlane,
    attribute_profile,
    device_memory_samples,
    is_oom_error,
    load_memory_summary,
    memory_metrics,
    parse_memory_profile,
    tree_bytes_by_subsystem,
)
from neuronx_distributed_training_tpu.telemetry.recompile import RecompileDetector
from neuronx_distributed_training_tpu.telemetry.tensorstats import (
    HIST_PREFIX as TENSORSTATS_HIST_PREFIX,
    SCALAR_PREFIX as TENSORSTATS_SCALAR_PREFIX,
    TensorStatsConfig,
    decode_cum,
    init_tensorstats_state,
    tensorstats_state_specs,
    tensorstats_update,
)
from neuronx_distributed_training_tpu.telemetry.spans import (
    NON_PRODUCTIVE_SPANS,
    SpanTimer,
)
from neuronx_distributed_training_tpu.telemetry.step_timeline import (
    analyze_pipeline,
    pipeline_facts,
)
from neuronx_distributed_training_tpu.telemetry.trace import (
    TraceCapture,
    TraceConfig,
)
from neuronx_distributed_training_tpu.telemetry.trace_analysis import (
    analyze_trace_dir,
    load_trace_summary,
)

__all__ = [
    "ALERT_ACTIONS",
    "AlertEngine",
    "AlertRule",
    "FleetAggregator",
    "FleetBeacon",
    "FleetConfig",
    "FleetPlane",
    "HEALTH_POLICIES",
    "HangWatchdog",
    "HealthConfig",
    "HealthMonitor",
    "MEMORY_SUMMARY_NAME",
    "MemoryConfig",
    "MemoryPlane",
    "NON_PRODUCTIVE_SPANS",
    "SUBSYSTEMS",
    "RecompileDetector",
    "SpanTimer",
    "TELEMETRY_KNOBS",
    "TENSORSTATS_HIST_PREFIX",
    "TENSORSTATS_SCALAR_PREFIX",
    "TelemetryConfig",
    "TensorStatsConfig",
    "TraceCapture",
    "TraceConfig",
    "aggregate_fleet",
    "analyze_pipeline",
    "analyze_trace_dir",
    "attribute_profile",
    "compile_census",
    "decode_cum",
    "device_memory_samples",
    "grad_group_of",
    "init_tensorstats_state",
    "is_oom_error",
    "load_memory_summary",
    "load_trace_summary",
    "memory_analysis_bytes",
    "memory_metrics",
    "parse_alerts",
    "parse_memory_profile",
    "pipeline_facts",
    "tensorstats_state_specs",
    "tensorstats_update",
    "tree_bytes_by_subsystem",
]
