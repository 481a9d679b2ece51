"""The nine per-layer metrics that read the start-up timeline (``startup`` of
the trainer's ``run_summary.json``; docs/observability.md "Start-up
timeline"): each ``layer_metrics/<name>.json`` is data for the accepted
dotted-key reader, and each reads a number from a rehearsed traced run.

A pytest worker's first ``fit()`` may have run in any earlier test, so the
rehearsal is given a fresh timeline: the run below is "the first fit() of a
process" whose start lies back at the worker's."""

import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark.harness import cell as cells
from benchmark.harness import drive
from benchmark.readers import run_summary

BENCH = cells.load_benchmark()
# the harness keeps one run directory a cell and mode: no other test file
# rehearses this cell traced, so no other xdist worker shares it
CELL = "mistral7b-pretrain-4k"
KEYS = {
    "startup_before_program_s": "startup.seconds.before_program",
    "startup_imports_s": "startup.seconds.imports",
    "startup_assemble_s": "startup.seconds.assemble",
    "startup_init_state_s": "startup.seconds.init_state",
    "startup_exp_manager_s": "startup.seconds.exp_manager",
    "first_step_s": "startup.seconds.first_step",
    "trace_lower_s": "startup.seconds.trace_lower",
    "startup_unattributed_pct": "startup.unattributed_pct",
    "compile_cache_misses": "startup.compile_cache.cache_misses",
}


@pytest.fixture(scope="module")
def traced():
    from neuronx_distributed_training_tpu.telemetry import recompile, spans

    mp = pytest.MonkeyPatch()
    mp.setattr(spans, "STARTUP", spans.StartupTimeline())
    mp.setattr(recompile, "COMPILES", recompile.CompileLog())
    try:
        cell = toy(cells.load_cell(CELL))
        yield drive.run_cell(
            cell, seed=2**31 + 41, seconds=1.0, trace=True,
            t_process=time.perf_counter(), require_tpu=False,
            limits=toy_limits(cell))
    finally:
        mp.undo()


@pytest.mark.parametrize("name", sorted(KEYS))
def test_the_metric_is_data_for_the_accepted_reader(name):
    spec = cells.load_layer_metric(name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert spec["reader"] == "run_summary" and spec["args"] == {"key": KEYS[name]}
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "trainer loop", "setup_s", "lower")
    assert "workloads" not in entry          # every cell reports setup_s
    assert entry["source"] == ("program_counter" if name == "compile_cache_misses"
                               else "program_span")
    assert entry["unit"] == {"startup_unattributed_pct": "%",
                             "compile_cache_misses": "count"}.get(name, "s")
    # appended: the accepted entries keep their places
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("moe_load_max_share_p95")


@pytest.mark.parametrize("name", sorted(KEYS))
def test_the_metric_reads_a_number_from_a_rehearsed_run(traced, name):
    assert traced["correct"] is True
    got = traced["metrics"][name]
    assert got["unit"] == cells.load_layer_metric(name)["unit"]
    assert got["value"] >= 0.0
    # (the imports were made long before this worker's fresh timeline: 0)
    if name in ("startup_assemble_s", "startup_init_state_s",
                "startup_exp_manager_s", "first_step_s", "trace_lower_s"):
        assert got["value"] > 0.0
    if name == "startup_unattributed_pct":
        assert got["value"] <= 100.0
    # the accepted compile_s stays beside them
    assert traced["metrics"]["compile_s"]["value"] > 0.0


def test_a_summary_from_before_the_timeline_reads_nothing():
    # the parent's program writes no ``startup`` section: every one of the
    # nine is left out of its traced line, none raises
    for key in KEYS.values():
        assert run_summary.read({"summary": {"compile_seconds": 3.0}}, key=key) is None
        assert run_summary.read({"summary": {"startup": {}}}, key=key) is None
