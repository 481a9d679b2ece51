"""Device-time trace analytics (telemetry.trace / trace_analysis) + the
planner's measured-overlap calibration loop: knob validation, the Chrome-
trace parser on a committed fixture (overlapping intervals, multi-device
lanes, async -start/-done halves, unknown op names), the guarded global
profiler session (the double-stop teardown hazard), a live CPU-captured
trace through real tiny-llama ``fit()``, and cost-model ranking shifts when
the calibration changes — all tier-1 / CPU."""

import gzip
import importlib.util
import json
import shutil
from pathlib import Path

import pytest

from neuronx_distributed_training_tpu.telemetry import TraceConfig
from neuronx_distributed_training_tpu.telemetry import trace as trace_mod
from neuronx_distributed_training_tpu.telemetry import trace_analysis as ta
from neuronx_distributed_training_tpu.utils.debug import collective_kind_of

FIXTURE = Path(__file__).parent / "data" / "device_trace_fixture.trace.json"


@pytest.fixture(autouse=True)
def _reset_session_guard():
    """The profiler session guard is process-global state; tests must not
    leak an owner into each other."""
    trace_mod._SESSION_OWNER = None
    yield
    trace_mod._SESSION_OWNER = None


# ---------------------------------------------------------------------------
# collective-kind matching (census <-> trace analytics alignment)
# ---------------------------------------------------------------------------


class TestCollectiveKindOf:
    def test_plain_and_uniquified(self):
        assert collective_kind_of("all-reduce") == "all-reduce"
        assert collective_kind_of("all-reduce.17") == "all-reduce"
        assert collective_kind_of("reduce-scatter.3") == "reduce-scatter"
        assert collective_kind_of("collective-permute") == "collective-permute"

    def test_async_start_counts_done_does_not(self):
        # the same single-count convention as the HLO text census
        assert collective_kind_of("all-gather-start.4") == "all-gather"
        assert collective_kind_of("all-gather-done.4") is None
        assert collective_kind_of("all-reduce-done") is None

    def test_non_collectives(self):
        for name in ("dot.3", "fusion.12", "reduce.8", "reduce-window",
                     "all-reducer", "my-all-reduce", "while"):
            assert collective_kind_of(name) is None, name


# ---------------------------------------------------------------------------
# exp_manager.telemetry.trace knob validation
# ---------------------------------------------------------------------------


class TestTraceConfig:
    def test_defaults_disabled(self):
        tc = TraceConfig.from_config(None)
        assert not tc.enabled
        assert tc.start_step == 1 and tc.num_steps == 3 and not tc.keep_raw

    def test_bool_shortcut(self):
        assert TraceConfig.from_config(True).enabled
        assert not TraceConfig.from_config(False).enabled

    def test_unknown_key_did_you_mean(self):
        with pytest.raises(ValueError, match="start_step"):
            TraceConfig.from_config({"start_stepp": 2})

    def test_non_bool_rejected(self):
        with pytest.raises(ValueError, match="boolean"):
            TraceConfig.from_config({"keep_raw": "yes"})

    def test_window_bounds(self):
        with pytest.raises(ValueError, match="num_steps"):
            TraceConfig.from_config({"num_steps": 0})
        with pytest.raises(ValueError, match="start_step"):
            TraceConfig.from_config({"start_step": -1})

    def test_rejected_at_config_load(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = {"exp_manager": {"telemetry": {"trace": {"num_stepz": 2}}},
               "data": {"global_batch_size": 8, "micro_batch_size": 1}}
        with pytest.raises(ValueError, match="num_stepz"):
            load_config(cfg)

    def test_round_trip_through_telemetry_config(self):
        from neuronx_distributed_training_tpu.telemetry import TelemetryConfig

        tc = TelemetryConfig.from_config(
            {"trace": {"enabled": True, "start_step": 5, "num_steps": 2,
                       "keep_raw": True}})
        assert tc.trace == TraceConfig(enabled=True, start_step=5,
                                       num_steps=2, keep_raw=True)
        # blanket off leaves the opt-in trace block at its default
        assert not TelemetryConfig.from_config(False).trace.enabled


# ---------------------------------------------------------------------------
# the parser, on the committed fixture
# ---------------------------------------------------------------------------


@pytest.fixture()
def fixture_summary():
    return ta.analyze_events(
        json.loads(FIXTURE.read_text())["traceEvents"], top_k=10)


class TestTraceAnalysisFixture:
    def test_lane_and_name_filtering(self, fixture_summary):
        s = fixture_summary
        # 6 real device ops survive: runtime noise (::), unknown-cased
        # names, zero-duration events, -done halves, and host-lane events
        # with op-like names are all dropped
        assert s["num_op_events"] == 6
        assert s["devices"] == ["/device:TPU:0", "/device:TPU:1"]

    def test_overlap_merges_concurrent_compute(self, fixture_summary):
        # dev0 compute [0,100) and [80,180) merge to [0,180): the
        # all-reduce at [150,250) hides exactly 30us, not 50
        ar = fixture_summary["overlap_by_class"]["all-reduce"]
        assert ar["count"] == 2
        assert ar["wire_seconds"] == pytest.approx(130e-6)
        assert ar["hidden_seconds"] == pytest.approx(60e-6)
        assert ar["exposed_seconds"] == pytest.approx(70e-6)
        assert ar["achieved_overlap"] == pytest.approx(60 / 130, abs=1e-6)

    def test_multi_device_lanes_do_not_cross_hide(self, fixture_summary):
        # the all-gather on dev0 [300,350) has no concurrent dev0 compute;
        # dev1's compute must not hide it
        ag = fixture_summary["overlap_by_class"]["all-gather"]
        assert ag["wire_seconds"] == pytest.approx(50e-6)
        assert ag["hidden_seconds"] == 0.0
        assert ag["achieved_overlap"] == 0.0

    def test_totals_and_overall_overlap(self, fixture_summary):
        s = fixture_summary
        assert s["compute_seconds"] == pytest.approx(250e-6)
        assert s["collective_seconds"] == pytest.approx(180e-6)
        assert s["hidden_collective_seconds"] == pytest.approx(60e-6)
        assert s["exposed_collective_seconds"] == pytest.approx(120e-6)
        assert s["achieved_overlap"] == pytest.approx(1 / 3, abs=1e-5)
        assert s["total_device_seconds"] == pytest.approx(430e-6)

    def test_top_ops_table(self, fixture_summary):
        top = fixture_summary["top_ops"]
        assert top[0]["op"] == "dot" and top[0]["count"] == 2
        assert top[0]["total_seconds"] == pytest.approx(150e-6)
        assert top[0]["class"] == "compute"
        assert top[0]["share"] == pytest.approx(150 / 430, abs=1e-5)
        by_op = {o["op"]: o for o in top}
        assert by_op["all-reduce"]["class"] == "all-reduce"
        # async -start halves keep their name but classify by kind
        assert by_op["all-gather-start"]["class"] == "all-gather"

    def test_per_step_attribution(self, fixture_summary):
        steps = fixture_summary["steps"]
        assert set(steps) == {"0", "1"}
        s0, s1 = steps["0"], steps["1"]
        assert s0["compute_seconds"] == pytest.approx(250e-6)
        assert s0["collective_seconds"] == pytest.approx(80e-6)
        assert s0["device_seconds"] == pytest.approx(330e-6)
        # step 1 holds the all-reduce tail [200,250) + the whole all-gather
        assert s1["compute_seconds"] == 0.0
        assert s1["collective_seconds"] == pytest.approx(100e-6)

    def test_no_collectives_means_null_overlap(self):
        evs = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10,
             "name": "dot.1"},
        ]
        s = ta.analyze_events(evs)
        assert s["achieved_overlap"] is None
        assert s["collective_seconds"] == 0.0

    def test_load_from_gz_and_directory(self, tmp_path, fixture_summary):
        # the capture-dir layout jax.profiler writes, gzipped
        d = tmp_path / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            f.write(FIXTURE.read_text())
        s = ta.analyze_trace_dir(tmp_path)
        assert s["num_op_events"] == fixture_summary["num_op_events"]
        assert s["achieved_overlap"] == fixture_summary["achieved_overlap"]

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ta.load_trace_events(tmp_path)


# ---------------------------------------------------------------------------
# the guarded global profiler session (double-stop hazard regression)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    """Counts start/stop calls and raises on a stop without a live trace —
    exactly jax.profiler's behavior, minus the profiler."""

    def __init__(self):
        self.starts = 0
        self.stops = 0
        self.active = False

    def start_trace(self, log_dir):
        if self.active:
            raise RuntimeError("profiler already started")
        self.active = True
        self.starts += 1

    def stop_trace(self):
        if not self.active:
            raise RuntimeError("No profiler session active")
        self.active = False
        self.stops += 1


@pytest.fixture()
def fake_profiler(monkeypatch):
    import jax

    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


class TestSessionGuard:
    def test_start_stop_round_trip(self, tmp_path, fake_profiler):
        assert trace_mod.start_session(str(tmp_path), "a")
        assert trace_mod.session_owner() == "a"
        assert trace_mod.stop_session("a")
        assert trace_mod.session_owner() is None
        assert fake_profiler.starts == 1 and fake_profiler.stops == 1

    def test_second_owner_refused_not_raised(self, tmp_path, fake_profiler):
        assert trace_mod.start_session(str(tmp_path), "a")
        assert not trace_mod.start_session(str(tmp_path), "b")
        assert fake_profiler.starts == 1  # jax never saw the second start

    def test_stop_by_non_owner_is_noop(self, tmp_path, fake_profiler):
        assert trace_mod.start_session(str(tmp_path), "a")
        assert not trace_mod.stop_session("b")
        assert fake_profiler.stops == 0
        assert trace_mod.stop_session("a")

    def test_double_stop_never_raises(self, tmp_path, fake_profiler):
        assert trace_mod.start_session(str(tmp_path), "a")
        assert trace_mod.stop_session("a")
        assert not trace_mod.stop_session("a")  # the old teardown crash
        assert fake_profiler.stops == 1

    def test_out_of_band_stop_swallowed(self, tmp_path, fake_profiler):
        import jax

        assert trace_mod.start_session(str(tmp_path), "a")
        jax.profiler.stop_trace()  # someone else closed the global session
        assert not trace_mod.stop_session("a")  # logged, not raised


class TestExpManagerProfileAlias:
    """``exp_manager.profile_start_step`` / ``profile_num_steps`` is an alias
    for the one ``telemetry.trace`` window (raw artifacts kept)."""

    def _exp(self, tmp_path, **kw):
        from neuronx_distributed_training_tpu.trainer.exp_manager import (
            ExpManager,
        )

        return ExpManager(exp_dir=str(tmp_path), log_files=False,
                          create_tensorboard_logger=False, **kw)

    def test_teardown_after_closed_window_does_not_double_stop(
            self, tmp_path, fake_profiler):
        """The regression the legacy window carried: its stop at window end
        vs the teardown stop in close() — close() after a closed window must
        be a no-op, not a second stop_trace (which raises)."""
        exp = self._exp(tmp_path, profile_start_step=1, profile_num_steps=1)
        assert exp._trace.cfg == TraceConfig(
            enabled=True, start_step=1, num_steps=1, keep_raw=True)
        exp.maybe_trace(0)     # before the window: untouched
        assert fake_profiler.starts == 0
        exp.maybe_trace(1)     # window opens
        assert fake_profiler.starts == 1 and exp.trace_active
        exp.maybe_trace(2)     # window closes
        assert fake_profiler.stops == 1 and not exp.trace_active
        exp.close()            # must not stop again (and must not raise)
        assert fake_profiler.stops == 1

    def test_teardown_closes_a_still_open_window_once(self, tmp_path,
                                                      fake_profiler):
        exp = self._exp(tmp_path, profile_start_step=1, profile_num_steps=5)
        exp.maybe_trace(1)
        exp.close()
        assert fake_profiler.stops == 1
        exp.close()  # idempotent
        assert fake_profiler.stops == 1

    def test_alias_yields_to_a_configured_trace_block(self, tmp_path,
                                                      fake_profiler, caplog):
        # jax allows one global session: with both set there is one window,
        # telemetry.trace's; the alias is logged and skipped
        from neuronx_distributed_training_tpu.telemetry import TelemetryConfig

        block = TraceConfig(enabled=True, start_step=5, num_steps=1)
        with caplog.at_level("WARNING"):
            exp = self._exp(tmp_path, profile_start_step=1,
                            profile_num_steps=1,
                            telemetry=TelemetryConfig(trace=block))
        assert "profile_start_step=1 skipped" in caplog.text
        assert exp._trace.cfg is block
        exp.maybe_trace(1)
        assert fake_profiler.starts == 0  # the alias's step: no session
        exp.maybe_trace(5)
        assert fake_profiler.starts == 1  # only the block's
        exp.close()
        assert fake_profiler.stops == 1


class TestTraceCaptureWindow:
    def _capture(self, tmp_path, monkeypatch, **cfg_kw):
        from neuronx_distributed_training_tpu.telemetry.trace import (
            TraceCapture,
        )

        def fake_start(log_dir, owner):
            # stand in for jax: "capture" by materializing the fixture
            d = Path(log_dir) / "plugins" / "profile" / "t0"
            d.mkdir(parents=True, exist_ok=True)
            shutil.copy(FIXTURE, d / "host.trace.json")
            return True

        monkeypatch.setattr(trace_mod, "start_session", fake_start)
        monkeypatch.setattr(trace_mod, "stop_session", lambda owner: True)
        return TraceCapture(TraceConfig(enabled=True, **cfg_kw), tmp_path)

    def test_window_produces_summary_and_cleans_raw(self, tmp_path,
                                                    monkeypatch):
        cap = self._capture(tmp_path, monkeypatch, start_step=2, num_steps=2)
        assert cap.maybe_update(0) is None
        assert cap.maybe_update(2) is None and cap.active
        assert cap.maybe_update(3) is None and cap.active
        summary = cap.maybe_update(4)
        assert summary is not None and cap.done
        assert summary["achieved_overlap"] == pytest.approx(1 / 3, abs=1e-5)
        assert summary["window"] == {"start_step": 2, "num_steps": 2}
        on_disk = json.loads((tmp_path / "trace_summary.json").read_text())
        assert on_disk["achieved_overlap"] == summary["achieved_overlap"]
        assert not (tmp_path / "trace").exists()  # keep_raw=False default
        assert cap.maybe_update(5) is None  # one window only

    def test_keep_raw(self, tmp_path, monkeypatch):
        cap = self._capture(tmp_path, monkeypatch, start_step=0, num_steps=1,
                            keep_raw=True)
        cap.maybe_update(0)
        assert cap.maybe_update(1) is not None
        assert (tmp_path / "trace").exists()

    def test_close_inside_window_analyzes(self, tmp_path, monkeypatch):
        cap = self._capture(tmp_path, monkeypatch, start_step=0, num_steps=100)
        cap.maybe_update(0)
        summary = cap.close()
        assert summary is not None
        assert (tmp_path / "trace_summary.json").exists()
        assert cap.close() is None  # idempotent

    def test_disabled_is_inert(self, tmp_path):
        from neuronx_distributed_training_tpu.telemetry.trace import (
            TraceCapture,
        )

        cap = TraceCapture(TraceConfig(enabled=False), tmp_path)
        assert cap.maybe_update(1) is None and not cap.active
        assert cap.close() is None

    def test_busy_session_retries_within_window(self, tmp_path, monkeypatch):
        """A refused session (e.g. a legacy profile window still holds the
        global profiler) must retry at the next in-window step, not abandon
        the whole window."""
        from neuronx_distributed_training_tpu.telemetry.trace import (
            TraceCapture,
        )

        busy = {"until": 3}

        def fake_start(log_dir, owner):
            if busy["until"] > 0:
                busy["until"] -= 1
                return False
            d = Path(log_dir) / "plugins" / "profile" / "t0"
            d.mkdir(parents=True, exist_ok=True)
            shutil.copy(FIXTURE, d / "host.trace.json")
            return True

        monkeypatch.setattr(trace_mod, "start_session", fake_start)
        monkeypatch.setattr(trace_mod, "stop_session", lambda owner: True)
        cap = TraceCapture(TraceConfig(enabled=True, start_step=1,
                                       num_steps=2), tmp_path)
        busy["until"] = 1
        assert cap.maybe_update(1) is None and not cap.active  # refused
        assert cap.maybe_update(2) is None and cap.active      # retried, won
        assert cap.maybe_update(3) is not None                 # window closed

    def test_window_fully_missed_gives_up_once(self, tmp_path, monkeypatch):
        from neuronx_distributed_training_tpu.telemetry.trace import (
            TraceCapture,
        )

        calls = {"n": 0}

        def always_busy(log_dir, owner):
            calls["n"] += 1
            return False

        monkeypatch.setattr(trace_mod, "start_session", always_busy)
        cap = TraceCapture(TraceConfig(enabled=True, start_step=1,
                                       num_steps=2), tmp_path)
        for step in range(6):
            assert cap.maybe_update(step) is None
        assert cap.done and calls["n"] == 2  # one try per in-window step


# ---------------------------------------------------------------------------
# measured-overlap calibration of the autotune cost model
# ---------------------------------------------------------------------------


def _facts(chips_cfg=None):
    from neuronx_distributed_training_tpu.autotune import ModelFacts
    from neuronx_distributed_training_tpu.config.loader import load_config

    cfg = {
        "name": "cal", "model_source": "hf",
        "distributed_strategy": {"tensor_model_parallel_size": 2,
                                 "zero1": True},
        "data": {"seq_length": 2048, "global_batch_size": 64,
                 "micro_batch_size": 1},
        "model": {"architecture": "llama", "vocab_size": 32000,
                  "hidden_size": 2048, "intermediate_size": 5504,
                  "num_layers": 16, "num_attention_heads": 16,
                  "num_key_value_heads": 8,
                  "max_position_embeddings": 2048},
        "precision": {"type": "mixed_precision"},
    }
    cfg.update(chips_cfg or {})
    return ModelFacts.from_config(load_config(cfg)), cfg


class TestOverlapCalibration:
    def test_no_hardcoded_constant_left(self):
        from neuronx_distributed_training_tpu.autotune import cost_model

        assert not hasattr(cost_model, "_COMMS_OVERLAP")

    def test_resolve_overlap_forms(self):
        from neuronx_distributed_training_tpu.autotune import resolve_overlap
        from neuronx_distributed_training_tpu.autotune.topology import (
            TOPOLOGIES,
        )

        topo = TOPOLOGIES["v5e"]
        assert resolve_overlap(None, topo)["default"] == topo.comms_overlap
        assert resolve_overlap(0.8, topo)["tp"] == 0.8
        got = resolve_overlap({"tp": 0.7, "default": 0.2}, topo)
        assert got["tp"] == 0.7 and got["dp"] == 0.2 and got["pp"] == 0.2
        # a measured 1.0 must not price comms as free
        assert resolve_overlap(1.0, topo)["tp"] == 0.99

    def test_topology_table_carries_per_generation_defaults(self):
        from neuronx_distributed_training_tpu.autotune.topology import (
            TOPOLOGIES,
        )

        overlaps = {t.comms_overlap for t in TOPOLOGIES.values()}
        assert len(overlaps) > 1  # a table, not one constant in disguise
        assert all(0.0 < v < 1.0 for v in overlaps)

    def test_estimate_plan_prices_overlap(self):
        from neuronx_distributed_training_tpu.autotune import estimate_plan
        from neuronx_distributed_training_tpu.autotune.topology import (
            TOPOLOGIES,
        )

        facts, _ = _facts()
        plan = facts.declared_plan_for(8)
        topo = TOPOLOGIES["v5e"]
        lo = estimate_plan(facts, plan, topo, overlap=0.1)
        hi = estimate_plan(facts, plan, topo, overlap=0.9)
        assert lo.comms_seconds > hi.comms_seconds > 0
        # exposed time scales with (1 - overlap)
        assert lo.comms_seconds == pytest.approx(
            hi.comms_seconds * (1 - 0.1) / (1 - 0.9), rel=1e-6)
        # default pricing == the topology table's prior
        assert estimate_plan(facts, plan, topo).comms_seconds == (
            pytest.approx(estimate_plan(
                facts, plan, topo, overlap=topo.comms_overlap).comms_seconds))

    def test_calibration_shifts_the_ranking(self):
        """The acceptance bar: a changed measured overlap must be able to
        REORDER plans, not just rescale them — pp-heavy meshes (cheap hops,
        bubble-bound) win when little hiding is measured; wide-tp meshes win
        when the scheduler hides most of the wire time."""
        from neuronx_distributed_training_tpu.autotune import rank_plans
        from neuronx_distributed_training_tpu.autotune.topology import (
            TOPOLOGIES,
        )

        facts, _ = _facts()
        topo = TOPOLOGIES["v5e"]
        lo, _, _ = rank_plans(facts, 16, topo, overlap=0.05)
        hi, _, _ = rank_plans(facts, 16, topo, overlap=0.95)
        assert lo[0].plan.mesh != hi[0].plan.mesh
        assert lo[0].plan.pp > 1       # exposed comms -> pipeline hops win
        assert hi[0].plan.pp == 1      # hidden comms -> flat wide mesh wins

    def test_overlap_from_trace_summary(self, fixture_summary):
        from neuronx_distributed_training_tpu.autotune import (
            overlap_from_trace_summary,
        )

        got = overlap_from_trace_summary(fixture_summary)
        assert got["default"] == pytest.approx(1 / 3, abs=1e-5)
        # tp/dp take the wire-weighted AG+RS+AR overlap: (0 + 60)/(50 + 130)
        assert got["tp"] == pytest.approx(60 / 180, abs=1e-6)
        assert got["dp"] == pytest.approx(60 / 180, abs=1e-6)
        # classes absent from the trace fall back to default at resolve time
        assert "pp" not in got and "ep" not in got

    def test_overlap_from_summary_requires_collectives(self):
        from neuronx_distributed_training_tpu.autotune import (
            overlap_from_trace_summary,
        )

        with pytest.raises(ValueError, match="calibrate"):
            overlap_from_trace_summary({"overlap_by_class": {}})

    def test_malformed_class_entry_is_valueerror_not_crash(self, tmp_path):
        # a hand-edited/schema-drifted summary must become a report error
        # (plan_config catches ValueError), never a CLI traceback
        from neuronx_distributed_training_tpu.autotune import (
            overlap_from_trace_summary,
            plan_config,
        )

        bad = {"achieved_overlap": 0.5,
               "overlap_by_class": {"all-gather": 0.7}}
        with pytest.raises(ValueError, match="overlap_by_class"):
            overlap_from_trace_summary(bad)
        _, cfg = _facts()
        p = tmp_path / "trace_summary.json"
        p.write_text(json.dumps(bad))
        rep = plan_config(cfg, chips=8, topology="v5e", audit=False,
                          calibration=str(p))
        assert rep.error and "calibration" in rep.error

    def test_plan_config_calibration_path(self, tmp_path, fixture_summary):
        from neuronx_distributed_training_tpu.autotune import plan_config

        _, cfg = _facts()
        p = tmp_path / "trace_summary.json"
        p.write_text(json.dumps(fixture_summary))
        rep = plan_config(cfg, chips=8, topology="v5e", audit=False,
                          top_k=3, calibration=str(p))
        assert rep.error is None
        assert rep.overlap["measured"] is True
        assert rep.overlap["tp"] == pytest.approx(60 / 180, abs=1e-4)
        assert "overlap" in rep.to_dict()
        # un-calibrated: the topology prior, marked as such
        rep2 = plan_config(cfg, chips=8, topology="v5e", audit=False,
                           top_k=3)
        assert rep2.overlap["measured"] is False
        assert rep2.overlap["tp"] == pytest.approx(0.5)

    def test_plan_config_bad_calibration_is_report_error(self, tmp_path):
        from neuronx_distributed_training_tpu.autotune import plan_config

        _, cfg = _facts()
        rep = plan_config(cfg, chips=8, topology="v5e", audit=False,
                          calibration=str(tmp_path / "nope.json"))
        assert rep.error and "calibration" in rep.error


# ---------------------------------------------------------------------------
# live CPU-captured trace through real tiny-llama fit()
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory, devices8):
    """One tiny fit() with a real telemetry.trace window on the CPU backend;
    shared across the smoke assertions."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    tmp_path = tmp_path_factory.mktemp("traced_run")
    cfg = load_config({
        "name": "tr", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": 4, "log_every_n_steps": 1},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False,
                        "telemetry": {"trace": {"enabled": True,
                                                "start_step": 1,
                                                "num_steps": 2}}},
        "distributed_strategy": {"tensor_model_parallel_size": 2,
                                 "sequence_parallel": True},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        "model": {"vocab_size": 128, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    })
    t = Trainer.from_config(cfg, enable_checkpointing=False)
    metrics = t.fit()
    exp_dir = tmp_path / "exp" / "tr" / "version_0"
    summary = json.loads((exp_dir / "trace_summary.json").read_text())
    run_summary = json.loads((exp_dir / "run_summary.json").read_text())
    return t, metrics, summary, run_summary, exp_dir


class TestLiveTraceSmoke:
    def test_summary_written_with_real_collectives(self, traced_run):
        _, metrics, summary, _, _ = traced_run
        import numpy as np

        assert np.isfinite(metrics["loss"])
        # tp=2 + SP inserts real collectives; the CPU backend traces them
        assert summary["num_op_events"] > 0
        assert summary["collective_seconds"] > 0
        assert summary["overlap_by_class"], summary.keys()
        assert 0.0 <= summary["achieved_overlap"] <= 1.0
        for c in summary["overlap_by_class"].values():
            assert c["wire_seconds"] == pytest.approx(
                c["hidden_seconds"] + c["exposed_seconds"], rel=1e-6)

    def test_top_ops_and_window_steps(self, traced_run):
        _, _, summary, _, _ = traced_run
        assert summary["top_ops"] and summary["top_ops"][0]["total_seconds"] > 0
        # per-step attribution covers exactly the traced window [1, 3)
        assert set(summary["steps"]) <= {"1", "2"}
        assert summary["steps"], "no StepTraceAnnotation windows captured"
        assert summary["window"] == {"start_step": 1, "num_steps": 2}

    def test_raw_artifacts_cleaned_up(self, traced_run):
        *_, exp_dir = traced_run
        assert not (exp_dir / "trace").exists()  # keep_raw defaults off

    def test_run_summary_carries_trace_section(self, traced_run):
        _, _, summary, run_summary, _ = traced_run
        tr = run_summary["trace"]
        assert tr["achieved_overlap"] == summary["achieved_overlap"]
        assert tr["exposed_collective_seconds"] == (
            summary["exposed_collective_seconds"])
        assert tr["summary_path"].endswith("trace_summary.json")

    def test_comms_section_joins_live_wire_times(self, traced_run):
        # the interconnect observatory's in-loop layer: the cost model's
        # per-class byte volumes joined with the traced wire seconds into
        # achieved bus bandwidth + efficiency vs the topology peak
        from neuronx_distributed_training_tpu.telemetry.comms import (
            comms_metrics,
        )

        _, _, summary, run_summary, _ = traced_run
        section = summary.get("comms")
        assert section, "trace summary carries no comms section"
        assert section["window_steps"] == 2
        assert section["topology"] == "cpu"
        assert section["peak_bandwidth_gbps"] > 0
        for kind, e in section["classes"].items():
            assert kind in summary["overlap_by_class"]
            assert e["achieved_gbps"] > 0
            assert e["bus_bytes_per_step"] > 0
            assert e["wire_seconds_per_step"] > 0
            assert e["efficiency"] > 0
            assert e["count"] > 0
        # run_summary mirrors the section at the TOP level (where the perf
        # contract's run-dir extraction and tools/comms_report.py read it),
        # and the flattened scalars rode the metric stream to every sink
        assert run_summary["comms"] == section
        scalars = comms_metrics(section)
        kind = sorted(section["classes"])[0]
        assert f"comms/{kind}/achieved_gbps" in scalars
        assert f"comms/{kind}/efficiency" in scalars

    def test_calibrates_the_planner_end_to_end(self, traced_run):
        # the full loop: captured trace -> measured overlap -> plan pricing
        from neuronx_distributed_training_tpu.autotune import plan_config

        *_, exp_dir = traced_run
        _, cfg = _facts()
        rep = plan_config(cfg, chips=8, topology="v5e", audit=False,
                          top_k=2, calibration=str(exp_dir))
        assert rep.error is None and rep.overlap["measured"] is True


# ---------------------------------------------------------------------------
# tools/trace_report.py + metrics_report trace section
# ---------------------------------------------------------------------------


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceReportCLI:
    def test_renders_summary_file(self, tmp_path, fixture_summary, capsys):
        tr = _load_tool("trace_report")
        p = tmp_path / "trace_summary.json"
        p.write_text(json.dumps(fixture_summary))
        assert tr.main([str(p)]) == 0
        out = capsys.readouterr().out
        for needle in ("achieved_overlap", "all-reduce", "all-gather",
                       "top", "step 0", "hidden", "exposed",
                       "--calibrate-from"):
            assert needle in out, (needle, out)

    def test_renders_run_dir_and_json_contract(self, tmp_path,
                                               fixture_summary, capsys):
        tr = _load_tool("trace_report")
        (tmp_path / "trace_summary.json").write_text(
            json.dumps(fixture_summary))
        assert tr.main([str(tmp_path), "--json", "-"]) == 0
        out = capsys.readouterr().out
        payload = json.loads(out.strip().splitlines()[-1])  # last line = JSON
        assert payload["achieved_overlap"] == pytest.approx(1 / 3, abs=1e-5)

    def test_parses_raw_trace_file(self, tmp_path, capsys):
        tr = _load_tool("trace_report")
        assert tr.main([str(FIXTURE)]) == 0
        assert "achieved_overlap" in capsys.readouterr().out

    def test_missing_path_errors(self, tmp_path):
        tr = _load_tool("trace_report")
        assert tr.main([str(tmp_path / "nope.json")]) == 2

    def test_renders_real_run_output(self, traced_run, capsys):
        tr = _load_tool("trace_report")
        *_, exp_dir = traced_run
        assert tr.main([str(exp_dir)]) == 0
        assert "achieved_overlap" in capsys.readouterr().out


class TestMetricsReportTraceSection:
    def test_trace_summary_rendered_when_present(self, tmp_path,
                                                 fixture_summary, capsys):
        mr = _load_tool("metrics_report")
        with open(tmp_path / "metrics.jsonl", "w") as f:
            f.write(json.dumps({"step": 1, "loss": 5.0}) + "\n")
        (tmp_path / "run_summary.json").write_text(
            json.dumps({"compile_seconds": 1.0}))
        (tmp_path / "trace_summary.json").write_text(
            json.dumps(fixture_summary))
        assert mr.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        for needle in ("device-time trace", "achieved_overlap",
                       "trace_report.py", "dot"):
            assert needle in out, (needle, out)

    def test_absent_trace_summary_is_silent(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        with open(tmp_path / "metrics.jsonl", "w") as f:
            f.write(json.dumps({"step": 1, "loss": 5.0}) + "\n")
        assert mr.main([str(tmp_path)]) == 0
        assert "device-time trace" not in capsys.readouterr().out
