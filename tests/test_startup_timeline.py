"""The start-up timeline (telemetry/spans.py::STARTUP, docs/observability.md
"Start-up timeline"): process start to the end of the first logging boundary
as spans of one process-level timer, the compile listener armed from the
package's first import, and the ``startup`` section of ``run_summary.json``
— all tier-1 / CPU.

A pytest worker is one long process whose first ``fit()`` may have run in any
earlier test, so every test that needs "the first fit() of a process" installs
a fresh timeline and a fresh compile log."""

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_training_tpu.telemetry import (
    RecompileDetector,
    SpanTimer,
    recompile,
    spans,
)
from neuronx_distributed_training_tpu.telemetry.spans import (
    FIRST_STEP_SPAN,
    NON_PRODUCTIVE_SPANS,
    STARTUP_SPANS,
    StartupTimeline,
)

SHORT = [n.removeprefix("startup/") for n in STARTUP_SPANS]


@pytest.fixture
def fresh(monkeypatch):
    """A process that has just imported the package: an unclaimed timeline
    and an empty compile log."""
    monkeypatch.setattr(spans, "STARTUP", StartupTimeline())
    monkeypatch.setattr(recompile, "COMPILES", recompile.CompileLog())
    return spans.STARTUP


def _cfg(tmp_path, name="tl", **telemetry):
    from neuronx_distributed_training_tpu.config.loader import load_config

    return load_config({
        "name": name, "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": 3, "log_every_n_steps": 1},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False,
                        **({"telemetry": telemetry} if telemetry else {})},
        "distributed_strategy": {"tensor_model_parallel_size": 2},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        # widths no other test file uses: init_params really compiles here
        "model": {"vocab_size": 136, "hidden_size": 48,
                  "intermediate_size": 112, "num_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    })


def _fit(tmp_path, name="tl", **telemetry):
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    t = Trainer.from_config(_cfg(tmp_path, name, **telemetry),
                            enable_checkpointing=False)
    t.fit()
    return json.loads(
        (Path(t.exp.log_dir) / "run_summary.json").read_text())


# -- the origin ---------------------------------------------------------------


def _stat_file(tmp_path, age_s, comm="(python3 -m x) y)"):
    """A ``/proc/<pid>/stat`` whose process started ``age_s`` seconds ago."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = time.clock_gettime(time.CLOCK_BOOTTIME) - age_s
    fields = ["S"] + ["0"] * 18 + [str(int(start * ticks))] + ["0"] * 30
    path = tmp_path / "stat"
    path.write_text(f"4242 {comm} " + " ".join(fields) + "\n")
    return str(path)


def test_the_origin_is_the_process_start_where_proc_gives_it(tmp_path):
    now = time.perf_counter()
    tl = StartupTimeline(t_import=now, stat_path=_stat_file(tmp_path, 12.5))
    assert tl.origin == "process_start"
    # the tick is 1/100 s: the start is found to a few hundredths
    assert now - tl.timer._t_start == pytest.approx(12.5, abs=0.05)
    assert tl.timer.snapshot()["startup/before_program"] == pytest.approx(
        12.5, abs=0.05)
    assert tl.timer.wall_seconds >= 12.4


@pytest.mark.parametrize("stat", ["missing", "garbage", "future"])
def test_the_origin_falls_back_to_the_import_and_says_so(tmp_path, stat):
    path = tmp_path / "stat"
    if stat == "garbage":
        path.write_text("no parenthesis here\n")
    elif stat == "future":  # a start after the import: not this process's
        path = Path(_stat_file(tmp_path, -30.0))
    now = time.perf_counter()
    tl = StartupTimeline(t_import=now, stat_path=str(path))
    assert tl.origin == "package_import"
    assert tl.timer._t_start == now
    assert "startup/before_program" not in tl.timer.snapshot()


def test_the_package_stamps_its_first_import_before_anything_else():
    import neuronx_distributed_training_tpu as pkg

    live = spans.STARTUP if not spans.STARTUP.claimed else None
    assert isinstance(pkg._T_IMPORT, float)
    if live is not None and live.origin == "process_start":
        assert live.timer._t_start <= pkg._T_IMPORT


# -- phases -------------------------------------------------------------------


def test_overlapping_intervals_are_cut_disjoint_the_later_begun_wins():
    pieces = spans._disjoint(
        [("startup/fit_prologue", 10.0, 5.0), ("restart", 11.0, 2.0),
         ("startup/imports", 2.0, 3.0), ("startup/imports", 3.0, 1.0),
         ("compile", 16.0, 10.0)], 0.0, 20.0)
    assert pieces == [
        ("startup/imports", 2.0, 5.0),
        ("startup/fit_prologue", 10.0, 11.0), ("restart", 11.0, 13.0),
        ("startup/fit_prologue", 13.0, 15.0), ("compile", 16.0, 20.0)]


def _check_section(section):
    phases = section["phases"]
    # disjoint and in order
    for a, b in zip(phases, phases[1:]):
        assert a["begin_s"] + a["seconds"] <= b["begin_s"] + 1e-9, (a, b)
    assert all(p["seconds"] > 0 for p in phases)
    sec = section["seconds"]
    # with ``unattributed`` they sum to the whole, to the microsecond
    us = lambda s: round(s * 1e6)  # noqa: E731
    assert sum(us(p["seconds"]) for p in phases) + us(sec["unattributed"]) \
        == us(section["to_first_step_s"])
    # the flat map is the same phases by name, beside its two sums
    names = {p["name"] for p in phases}
    for name in names:
        assert us(sec[name]) == sum(
            us(p["seconds"]) for p in phases if p["name"] == name)
    assert set(sec) == set(SHORT) | {
        "restart", "compile", "first_step", "unattributed", "init_state",
        "trace_lower"}
    assert all(sec[k] == 0.0 for k in set(SHORT) - names)
    assert us(sec["init_state"]) == us(sec["init_params"]) + us(
        sec["init_opt_state"])
    assert section["unattributed_pct"] == pytest.approx(
        100.0 * sec["unattributed"] / section["to_first_step_s"], abs=1e-3)
    order = [p["name"] for p in phases]
    known = SHORT + ["restart", "compile", "first_step"]
    assert set(order) <= set(known) | {"replan"}
    assert order[-1] == "first_step"


def test_a_hand_built_timeline_adds_up(tmp_path):
    t0 = time.perf_counter() - 100.0
    tl = StartupTimeline(t_import=t0 + 1.0, stat_path=str(tmp_path / "none"))
    tl.timer._t_start = t0  # as if /proc had said so
    add = tl.timer.add
    add("startup/before_program", 1.0, begin=t0)
    add("startup/imports", 9.0, begin=t0 + 1.0)
    add("startup/assemble", 2.25, begin=t0 + 11.0)
    add("startup/fit_prologue", 4.0, begin=t0 + 20.0)
    loop = SpanTimer(earlier=tl.timer)
    loop.add("restart", 1.5, begin=t0 + 21.0)
    loop.add("data_wait", 0.2, begin=t0 + 24.0)
    loop.add("compile", 3.0, begin=t0 + 24.5)
    loop.add("dispatch", 0.01, begin=t0 + 28.0)
    loop.add("host_sync", 0.5, begin=t0 + 28.01)
    section = tl.section(recompile.CompileLog().summary())
    _check_section(section)
    sec = section["seconds"]
    assert section["to_first_step_s"] == pytest.approx(28.51)
    assert sec["fit_prologue"] == pytest.approx(2.5)   # less the restart
    assert sec["restart"] == pytest.approx(1.5)
    assert sec["compile"] == pytest.approx(3.0)
    assert sec["first_step"] == pytest.approx(0.51)
    assert sec["unattributed"] == pytest.approx(28.51 - 19.76)
    assert [p["name"] for p in section["phases"]] == [
        "before_program", "imports", "assemble", "fit_prologue", "restart",
        "fit_prologue", "compile", "first_step"]
    # the loop's timer continues the clock: one wall, one set of totals
    assert loop.wall_seconds == pytest.approx(tl.timer.wall_seconds, abs=0.01)
    assert loop.snapshot()["startup/imports"] == 9.0
    gp = loop.goodput_summary()
    assert gp["breakdown_seconds"]["startup"] == pytest.approx(
        1.0 + 9.0 + 2.25 + 4.0)
    assert set(gp["breakdown_seconds"]) == {"startup", "restart", "compile"}
    # nothing of it enters the per-boundary window
    assert not any(k.startswith("startup/") for k in loop.drain())
    tl.close()
    loop.add("dispatch", 0.01, begin=t0 + 30.0)  # the steady loop keeps none
    assert tl.timer._intervals is None


def test_phases_do_not_nest_and_end_with_the_first_boundary(fresh):
    with spans.startup_phase("startup/imports"):
        with spans.startup_phase("startup/backend"):   # the outer one counts
            assert spans.open_phase() == "startup/imports"
    assert set(fresh.timer.snapshot()) <= {
        "startup/before_program", "startup/imports"}
    with spans.timed_import("json"):
        import json as _json  # noqa: F401
    assert fresh.imports_s["json"] >= 0.0
    fresh.close()
    with spans.startup_phase("startup/assemble"):
        assert spans.open_phase() is None
    assert "startup/assemble" not in fresh.timer.snapshot()
    assert set(STARTUP_SPANS) <= set(NON_PRODUCTIVE_SPANS)
    assert FIRST_STEP_SPAN not in NON_PRODUCTIVE_SPANS   # a training step


# -- the compile listener -----------------------------------------------------


def test_the_listener_is_armed_by_the_import_and_tags_the_open_phase(fresh):
    log = recompile.COMPILES
    with spans.startup_phase("startup/init_params"):
        jax.jit(lambda x: x * 41.5 + 3)(jnp.ones((7, 3)))   # a new program
    jax.jit(lambda x: x * 43.5 - 3)(jnp.ones((7, 3)))       # under no span
    assert log.by_phase["startup/init_params"]["n"] >= 1
    assert log.by_phase["startup/init_params"]["seconds"] > 0.0
    assert log.by_phase["startup/init_params"]["trace_lower_s"] > 0.0
    assert log.by_phase["unattributed"]["n"] >= 1
    t = log.totals
    assert t["trace_s"] > 0 and t["lower_s"] > 0 and t["backend_s"] > 0
    assert t["backend_s"] == pytest.approx(
        sum(p["seconds"] for p in log.by_phase.values()))
    assert t["listener_calls"] >= 6
    assert set(t) == {"trace_s", "lower_s", "backend_s", "cache_retrieval_s",
                      "cache_hits", "cache_misses", "listener_calls"}


def test_the_cache_counters_follow_jaxs_events(fresh):
    log = recompile.COMPILES
    for _ in range(3):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    assert (log.totals["cache_hits"], log.totals["cache_misses"],
            log.totals["cache_retrieval_s"]) == (3, 1, 0.25)
    assert log.totals["listener_calls"] == 6 and not log.by_phase


def test_per_phase_totals_survive_the_cap_of_the_list(fresh):
    detector = RecompileDetector()
    detector.watch_compiles(lambda: 4)
    try:
        with spans.named("validate"):
            for _ in range(RecompileDetector.MAX_COMPILE_EVENTS + 10):
                jax.monitoring.record_event_duration_secs(
                    recompile._BACKEND_COMPILE_EVENT, 0.5)
    finally:
        detector.unwatch_compiles()
    assert recompile._compile_sink is None
    assert len(detector.compile_events) == RecompileDetector.MAX_COMPILE_EVENTS
    assert detector.compile_events[0] == {
        "step": 4, "seconds": 0.5, "phase": "validate"}
    assert recompile.COMPILES.by_phase["validate"] == {
        "n": 60, "seconds": 30.0, "trace_lower_s": 0.0}
    # detached: the log still counts, the list does not
    jax.monitoring.record_event_duration_secs(
        recompile._BACKEND_COMPILE_EVENT, 0.5)
    assert recompile.COMPILES.by_phase["unattributed"]["n"] == 1
    assert len(detector.compile_events) == 50


# -- fit() --------------------------------------------------------------------


@pytest.fixture
def first_and_second_fit(fresh, tmp_path, devices8):
    return _fit(tmp_path, "first"), _fit(tmp_path, "second")


def test_the_first_fit_writes_the_timeline_and_it_adds_up(first_and_second_fit):
    summary, _ = first_and_second_fit
    section = summary["startup"]
    _check_section(section)
    assert section["origin"] == "process_start"
    ran = [p["name"] for p in section["phases"]]
    assert [n for n in ran if n != "imports"] == [
        "before_program", "backend", "assemble", "init_params",
        "init_opt_state", "exp_manager", "telemetry_arming", "fit_prologue",
        "restart", "fit_prologue", "compile", "first_step"]
    sec = section["seconds"]
    # the census's number and the timeline's are one span's
    assert sec["compile"] == pytest.approx(summary["compile_seconds"], abs=2e-3)
    assert sec["checkpointer"] == 0.0      # off: the key stays, at 0
    # a compile fired inside init_params is placed there
    assert section["compiles"]["init_params"]["n"] >= 1
    assert section["compiles"]["compile"]["n"] >= 1
    assert sec["trace_lower"] > 0.0
    assert set(section["compile_cache"]) == set(recompile.COMPILES.totals)
    assert set(section) == {
        "origin", "to_first_step_s", "seconds", "unattributed_pct", "phases",
        "imports_s", "compiles", "compile_cache"}
    # no Checkpointer was built, so nothing loaded orbax
    assert "orbax.checkpoint" not in section["imports_s"]
    # the old keys of compile_events, and the phase beside them
    events = summary["compile_events"]
    assert events and all(
        set(e) == {"step", "seconds", "phase"} for e in events)
    assert any(e["step"] == 0 and e["phase"] == "compile" for e in events)


def test_the_first_fits_goodput_wall_starts_at_process_start(
        first_and_second_fit):
    summary, second = first_and_second_fit
    gp, section = summary["goodput"], summary["startup"]
    assert gp["wall_seconds"] > section["to_first_step_s"]
    before_loop = sum(section["seconds"][n] for n in SHORT)
    assert gp["breakdown_seconds"]["startup"] == pytest.approx(
        before_loop, abs=2e-3)
    assert gp["nonproductive_seconds"] >= before_loop
    # a second fit() in the same process takes nothing over
    assert "startup" not in second
    assert "startup" not in second["goodput"]["breakdown_seconds"]
    assert second["goodput"]["wall_seconds"] < section["to_first_step_s"]
    assert second["compile_events"]


def test_with_spans_and_goodput_off_no_section_is_written(
        fresh, tmp_path, devices8):
    summary = _fit(tmp_path, "off", spans=False, goodput=False)
    assert "startup" not in summary and "goodput" not in summary
    assert fresh.claimed and fresh.closed
    assert summary["compile_events"]      # the list is no part of the switch
    later = _fit(tmp_path, "later")       # and the timeline is spent
    assert "startup" not in later


def test_a_first_boundary_after_several_steps_holds_them_all(
        fresh, tmp_path, devices8):
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = _cfg(tmp_path, "every3")
    cfg["trainer"]["log_every_n_steps"] = 3
    t = Trainer.from_config(cfg, enable_checkpointing=False)
    t.fit()
    summary = json.loads(
        (Path(t.exp.log_dir) / "run_summary.json").read_text())
    _check_section(summary["startup"])
    assert summary["startup"]["seconds"]["first_step"] > 0.0


def test_compiles_in_window_reads_the_list_as_before():
    from benchmark.readers import compile_events

    class Cell:
        traffic = {"check_steps": 3, "warmup_steps": 2}

    events = [{"step": 0, "seconds": 3.0, "phase": "compile"},
              {"step": 5, "seconds": 0.2, "phase": "log_metrics"},
              {"step": 7, "seconds": 0.2, "phase": None},
              {"step": 9, "seconds": 0.2, "phase": "dispatch"}]
    ctx = {"summary": {"compile_events": events}, "cell": Cell,
           "rows": [{"step": s} for s in range(6, 9)]}
    assert compile_events.read(ctx) == 2.0
    old = [{k: e[k] for k in ("step", "seconds")} for e in events]
    assert compile_events.read({**ctx, "summary": {"compile_events": old}}) == 2.0
