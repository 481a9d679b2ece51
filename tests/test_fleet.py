"""Fleet observability plane: beacons, aggregator, alert engine, wiring.

Simulated-fleet harness: :func:`write_sim_fleet` writes N host beacon
streams with seeded skew / stalls / deaths, and the tests assert the
aggregator names the right host AND the right cause class — off hardware,
off multiprocessing.  The live half drives real tiny-llama ``fit()`` runs
(alert halt, beacon continuity across incarnations, the dispatch-ahead
contract with fleet + alerts enabled).

``python tests/test_fleet.py --regen-fixture`` regenerates the committed
``tests/data/fleet_fixture/`` streams the verify SKILL's
``fleet_monitor --json`` smoke reads.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_training_tpu.config.loader import load_config
from neuronx_distributed_training_tpu.telemetry import TelemetryConfig
from neuronx_distributed_training_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    parse_alerts,
)
from neuronx_distributed_training_tpu.telemetry.fleet import (
    FleetAggregator,
    FleetBeacon,
    FleetConfig,
    aggregate_fleet,
    beacon_path,
)

FIXTURE = Path(__file__).parent / "data" / "fleet_fixture"


# ---------------------------------------------------------------------------
# the simulated-fleet harness
# ---------------------------------------------------------------------------

#: wall seconds per boundary window in simulated streams
SIM_WINDOW = 300.0
SIM_T0 = 1_700_000_000.0


def write_sim_fleet(
    fleet_dir: str | Path,
    *,
    n_hosts: int = 4,
    n_steps: int = 8,
    straggler: int | None = 2,
    cause: str = "data_stall",
    quiet_host: int | None = None,
    quiet_after: int = 4,
    die_host: int | None = None,
    die_after: int = 6,
    close_clean: bool = True,
    window: float = SIM_WINDOW,
) -> Path:
    """Write ``n_hosts`` beacon streams with seeded behavior.

    The fleet is lockstep (every host reaches step ``s`` at nearly the same
    wall instant) — the straggler signature is in the SPANS: the seeded
    straggler accumulates its cause span (data_wait / checkpoint / plain
    busy time) while every other host accumulates ``host_sync`` (waiting at
    the rendezvous).  Per-host monotonic origins deliberately differ: the
    aggregator must never compare them across hosts.
    """
    fleet_dir = Path(fleet_dir)
    fleet_dir.mkdir(parents=True, exist_ok=True)
    for h in range(n_hosts):
        spans = {"data_wait": 0.0, "host_sync": 0.0, "checkpoint": 0.0}
        mono0 = 1000.0 + 7.77 * h  # incomparable origins, on purpose
        lines = []
        last_step = n_steps
        for s in range(1, n_steps + 1):
            if quiet_host == h and s > quiet_after:
                last_step = quiet_after
                break
            if die_host == h and s > die_after:
                last_step = die_after
                break
            is_straggler = straggler == h
            if is_straggler:
                spans["host_sync"] += 0.5
                if cause == "data_stall":
                    spans["data_wait"] += 0.6 * window
                elif cause == "checkpoint_blocked":
                    spans["checkpoint"] += 0.6 * window
                # compute_slow: the busy time is just... compute (no span)
            else:
                spans["host_sync"] += 0.93 * window
                spans["data_wait"] += 0.2
            mfu = 0.35 if is_straggler else 0.55 - 0.01 * h
            goodput = 0.62 if is_straggler else 0.90 - 0.01 * h
            lines.append(json.dumps({
                "host": h,
                "step": s,
                "t_mono": round(mono0 + s * window, 6),
                "t_wall": round(SIM_T0 + s * window + 0.05 * h, 6),
                "metrics": {"loss": round(8.0 - 0.2 * s, 4), "mfu": mfu,
                            "goodput_fraction": goodput,
                            "step_time": window / 10.0},
                "spans": {k: round(v, 6) for k, v in spans.items()},
            }))
        if die_host == h:
            lines.append(json.dumps({
                "host": h, "step": last_step,
                "t_mono": round(mono0 + (last_step + 1) * window, 6),
                "t_wall": round(SIM_T0 + (last_step + 0.1) * window, 6),
                "metrics": {},
                "last_exception": "RuntimeError: injected device loss",
            }))
        elif close_clean and quiet_host != h:
            lines.append(json.dumps({
                "host": h, "step": last_step,
                "t_mono": round(mono0 + (last_step + 0.01) * window, 6),
                "t_wall": round(SIM_T0 + last_step * window + 1.0, 6),
                "metrics": {}, "closing": True,
            }))
        (fleet_dir / f"host_{h}.jsonl").write_text("\n".join(lines) + "\n")
    return fleet_dir


def regen_fixture() -> None:
    """The committed fixture: 5 hosts, host 2 data-stalls, host 3 goes
    quiet after step 4, host 4 dies at step 6 — the fleet_monitor smoke
    must name all three."""
    import shutil

    shutil.rmtree(FIXTURE, ignore_errors=True)
    write_sim_fleet(FIXTURE, n_hosts=5, n_steps=8, straggler=2,
                    cause="data_stall", quiet_host=3, quiet_after=4,
                    die_host=4, die_after=6)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


class TestFleetConfig:
    def test_defaults_disabled(self):
        cfg = FleetConfig.from_config(None)
        assert not cfg.enabled
        assert cfg.stale_after_seconds == 600.0

    def test_bool_form(self):
        assert FleetConfig.from_config(True).enabled
        assert not FleetConfig.from_config(False).enabled

    def test_unknown_key_did_you_mean(self):
        with pytest.raises(ValueError, match="stale_after_seconds"):
            FleetConfig.from_config({"stale_after_secs": 5})

    def test_bad_values(self):
        with pytest.raises(ValueError, match="stale_after_seconds"):
            FleetConfig.from_config({"stale_after_seconds": 0})
        with pytest.raises(ValueError, match="max_windows"):
            FleetConfig.from_config({"max_windows": 0})
        with pytest.raises(ValueError, match="boolean"):
            FleetConfig.from_config({"enabled": "yes"})
        with pytest.raises(ValueError, match="mapping"):
            FleetConfig.from_config([1])

    def test_nested_in_telemetry(self):
        tc = TelemetryConfig.from_config(
            {"fleet": {"enabled": True, "stale_after_seconds": 5.0},
             "batch_stats": True})
        assert tc.fleet.enabled and tc.fleet.stale_after_seconds == 5.0
        assert tc.batch_stats

    def test_telemetry_bool_keeps_fleet_disabled(self):
        assert not TelemetryConfig.from_config(True).fleet.enabled
        assert TelemetryConfig.from_config(True).alerts == ()

    def test_validated_at_config_load(self):
        with pytest.raises(ValueError, match="fleet"):
            load_config({"exp_manager": {"telemetry": {
                "fleet": {"enable": True}}}})


class TestAlertRules:
    def test_parse_minimal(self):
        (r,) = parse_alerts([{"metric": "loss", "threshold": 10.0}])
        assert r.name == "loss_threshold" and r.action == "log"
        assert r.window == 1 and r.mode == "threshold"

    def test_parse_full(self):
        rules = parse_alerts([
            {"metric": "data_wait", "window": 3, "threshold": 30.0,
             "action": "halt", "name": "dw"},
            {"metric": "mfu", "window": 5, "rel_drop": 0.2,
             "action": "dump"},
            {"metric": "loss", "below": 0.0},
        ])
        assert [r.mode for r in rules] == ["threshold", "rel_drop", "below"]
        assert rules[0].name == "dw"

    def test_none_and_empty(self):
        assert parse_alerts(None) == ()
        assert parse_alerts([]) == ()

    def test_not_a_list(self):
        with pytest.raises(ValueError, match="LIST"):
            parse_alerts({"metric": "loss", "threshold": 1})
        with pytest.raises(ValueError, match="LIST"):
            parse_alerts("loss")

    def test_missing_metric(self):
        with pytest.raises(ValueError, match="metric is required"):
            parse_alerts([{"threshold": 1.0}])

    def test_exactly_one_mode(self):
        with pytest.raises(ValueError, match="exactly ONE"):
            parse_alerts([{"metric": "loss"}])
        with pytest.raises(ValueError, match="exactly ONE"):
            parse_alerts([{"metric": "loss", "threshold": 1, "below": 0}])

    def test_bad_action_and_window(self):
        with pytest.raises(ValueError, match="action"):
            parse_alerts([{"metric": "loss", "threshold": 1,
                           "action": "page_oncall"}])
        with pytest.raises(ValueError, match="window"):
            parse_alerts([{"metric": "loss", "threshold": 1, "window": 0}])

    def test_rel_drop_range(self):
        with pytest.raises(ValueError, match="rel_drop"):
            parse_alerts([{"metric": "mfu", "rel_drop": 1.5}])

    def test_rel_rise_parses_and_ranges(self):
        (r,) = parse_alerts([{"metric": "data_wait", "rel_rise": 0.5}])
        assert r.mode == "rel_rise" and r.name == "data_wait_rel_rise"
        # unlike rel_drop there is no upper bound: 3.0 = "quadrupled"
        (r,) = parse_alerts([{"metric": "data_wait", "rel_rise": 3.0}])
        assert r.rel_rise == 3.0
        with pytest.raises(ValueError, match="rel_rise"):
            parse_alerts([{"metric": "data_wait", "rel_rise": 0.0}])
        with pytest.raises(ValueError, match="rel_rise"):
            parse_alerts([{"metric": "data_wait", "rel_rise": -0.2}])
        with pytest.raises(ValueError, match="exactly ONE"):
            parse_alerts([{"metric": "mfu", "rel_drop": 0.2,
                           "rel_rise": 0.2}])

    def test_rel_rise_did_you_mean(self):
        with pytest.raises(ValueError, match="rel_rise"):
            parse_alerts([{"metric": "loss", "rel_ris": 0.5}])

    def test_unknown_key_did_you_mean(self):
        with pytest.raises(ValueError, match="threshold"):
            parse_alerts([{"metric": "loss", "treshold": 1.0}])

    def test_duplicate_names(self):
        with pytest.raises(ValueError, match="duplicate"):
            parse_alerts([{"metric": "loss", "threshold": 1},
                          {"metric": "loss", "threshold": 2}])

    def test_validated_at_config_load(self):
        with pytest.raises(ValueError, match="alerts"):
            load_config({"exp_manager": {"telemetry": {
                "alerts": [{"metric": "loss"}]}}})


# ---------------------------------------------------------------------------
# beacons
# ---------------------------------------------------------------------------


class TestBeacon:
    def test_emit_lines_parse(self, tmp_path):
        b = FleetBeacon(tmp_path, host=3)
        b.emit(10, {"loss": 2.5, "mfu": 0.5, "health/nonfinite_count": 0,
                    "data/padding_fraction": 0.1, "grad_norm": 1.0},
               spans={"data_wait": 0.25})
        b.emit(20, {"loss": float("nan")})
        b.close()
        lines = beacon_path(tmp_path, 3).read_text().strip().splitlines()
        recs = [json.loads(l) for l in lines]
        assert recs[0]["host"] == 3 and recs[0]["step"] == 10
        assert recs[0]["metrics"]["loss"] == 2.5
        # health/ and data/ keys ride; unknown scalars don't
        assert "health/nonfinite_count" in recs[0]["metrics"]
        assert "data/padding_fraction" in recs[0]["metrics"]
        assert "grad_norm" not in recs[0]["metrics"]
        assert recs[0]["spans"]["data_wait"] == 0.25
        # strict JSON: NaN -> null, never a bare NaN token
        assert recs[1]["metrics"]["loss"] is None
        assert recs[-1]["closing"] is True

    def test_close_with_exception_marks_death(self, tmp_path):
        b = FleetBeacon(tmp_path, host=0)
        b.emit(1, {"loss": 1.0})
        b.close(last_exception="RuntimeError: boom", step=1)
        recs = [json.loads(l) for l in
                beacon_path(tmp_path, 0).read_text().strip().splitlines()]
        assert recs[-1]["last_exception"].startswith("RuntimeError")
        assert "closing" not in recs[-1]

    def test_emit_after_close_is_noop(self, tmp_path):
        b = FleetBeacon(tmp_path, host=0)
        b.close()
        b.emit(5, {"loss": 1.0})
        lines = beacon_path(tmp_path, 0).read_text().strip().splitlines()
        assert len(lines) == 1

    def test_torn_tail_line_skipped(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=2, n_steps=3, straggler=None)
        p = beacon_path(tmp_path, 0)
        with open(p, "a") as f:
            f.write('{"host": 0, "step": 99, "t_mono":')  # no newline: torn
        summary = aggregate_fleet(tmp_path)
        assert summary["hosts"]["0"]["last_step"] == 3

    def test_malformed_complete_line_skipped(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=1, n_steps=2, straggler=None)
        p = beacon_path(tmp_path, 0)
        with open(p, "a") as f:
            f.write("not json at all\n")
        summary = aggregate_fleet(tmp_path)
        assert summary["hosts"]["0"]["beacons"] == 3  # 2 + closing


# ---------------------------------------------------------------------------
# the aggregator on simulated fleets
# ---------------------------------------------------------------------------


class TestAggregatorStraggler:
    @pytest.mark.parametrize("cause", ["data_stall", "checkpoint_blocked",
                                       "compute_slow"])
    def test_names_straggler_and_cause(self, tmp_path, cause):
        write_sim_fleet(tmp_path, n_hosts=4, n_steps=6, straggler=2,
                        cause=cause)
        s = aggregate_fleet(tmp_path)
        assert s["straggler"] is not None, s["windows"]
        assert s["straggler"]["host"] == 2
        assert s["straggler"]["cause"] == cause
        # every attributed window agrees
        for w in s["windows"]:
            assert w["straggler_host"] == 2
            assert w["cause"] == cause

    def test_balanced_fleet_names_no_straggler(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=3, n_steps=5, straggler=None)
        s = aggregate_fleet(tmp_path)
        assert s["straggler"] is None
        assert all(w["straggler_host"] is None for w in s["windows"])

    def test_arrival_skew_reported(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=4, n_steps=4, straggler=1)
        s = aggregate_fleet(tmp_path)
        # seeded jitter: 0.05 * host -> skew 0.15 across 4 hosts
        assert s["windows"][-1]["arrival_skew_seconds"] == pytest.approx(
            0.15, abs=1e-6)

    def test_windows_capped(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=2, n_steps=30, straggler=1)
        agg = FleetAggregator(tmp_path, max_windows=5)
        s = agg.refresh()
        assert len(s["windows"]) == 5
        assert s["windows"][-1]["step"] == 30

    def test_monotonic_origins_never_compared(self, tmp_path):
        # host origins differ by ~8s in the sim; busy seconds must still be
        # window-duration-sized, not origin-delta-sized
        write_sim_fleet(tmp_path, n_hosts=3, n_steps=4, straggler=0)
        s = aggregate_fleet(tmp_path)
        for w in s["windows"]:
            for busy in w["busy_seconds"].values():
                assert 0.0 <= busy <= SIM_WINDOW * 1.01


class TestAggregatorQuietAndDead:
    def test_quiet_host_detected_with_cause(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=4, n_steps=8, straggler=None,
                        quiet_host=3, quiet_after=4)
        s = aggregate_fleet(tmp_path)
        assert [q["host"] for q in s["quiet_hosts"]] == [3]
        assert s["quiet_hosts"][0]["last_step"] == 4
        # 4 windows of silence at 300s >> the 600s default
        assert s["quiet_hosts"][0]["silent_seconds"] > 600
        stalls = [f for f in s["findings"] if f["kind"] == "fleet_stall"]
        assert len(stalls) == 1 and stalls[0]["host"] == 3
        assert "absence of progress" in stalls[0]["message"]

    def test_cleanly_closed_hosts_never_quiet(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=3, n_steps=4, straggler=None)
        # host 0's clean close landed long before "now"
        s = aggregate_fleet(tmp_path, now=SIM_T0 + 1e6)
        assert s["quiet_hosts"] == []

    def test_live_now_reference(self, tmp_path):
        # offline: newest beacon anchors staleness -> nobody quiet in a
        # freshly-stopped balanced fleet; live `now` far ahead -> an
        # UNCLOSED host is quiet
        write_sim_fleet(tmp_path, n_hosts=2, n_steps=3, straggler=None,
                        close_clean=False)
        assert aggregate_fleet(tmp_path)["quiet_hosts"] == []
        s = aggregate_fleet(tmp_path, now=SIM_T0 + 3 * SIM_WINDOW + 10_000)
        assert [q["host"] for q in s["quiet_hosts"]] == [0, 1]

    def test_dead_host_is_a_death_not_a_stall(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=3, n_steps=8, straggler=None,
                        die_host=2, die_after=3)
        s = aggregate_fleet(tmp_path)
        deaths = [f for f in s["findings"] if f["kind"] == "host_died"]
        assert len(deaths) == 1 and deaths[0]["host"] == 2
        assert "injected device loss" in deaths[0]["message"]
        assert all(q["host"] != 2 for q in s["quiet_hosts"])


class TestAggregatorSpreadAndGoodput:
    def test_spread_names_hosts(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=4, n_steps=5, straggler=2)
        s = aggregate_fleet(tmp_path)
        mfu = s["spread"]["mfu"]
        assert mfu["min"]["host"] == 2 and mfu["min"]["value"] == 0.35
        assert mfu["max"]["host"] == 0 and mfu["max"]["value"] == 0.55
        assert mfu["min"]["value"] <= mfu["p50"] <= mfu["max"]["value"]
        dw = s["spread"]["data_wait_seconds"]
        assert dw["max"]["host"] == 2  # the data-stall straggler

    def test_goodput_decomposition(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=4, n_steps=5, straggler=2)
        gp = aggregate_fleet(tmp_path)["goodput"]
        assert gp["worst_host"] == 2 and gp["best_host"] == 0
        assert gp["fleet_goodput_fraction"] == pytest.approx(0.62)
        assert gp["common_overhead_fraction"] == pytest.approx(0.10)
        assert gp["straggler_loss_fraction"] == pytest.approx(0.28)
        # the decomposition is exact: lost = common + straggler
        assert (gp["fleet_goodput_fraction"]
                + gp["common_overhead_fraction"]
                + gp["straggler_loss_fraction"]) == pytest.approx(1.0)

    def test_incremental_refresh(self, tmp_path):
        write_sim_fleet(tmp_path, n_hosts=2, n_steps=3, straggler=1,
                        close_clean=False)
        agg = FleetAggregator(tmp_path)
        s1 = agg.refresh()
        assert s1["hosts"]["0"]["last_step"] == 3
        n_windows = len(s1["windows"])
        # append two more steps to each stream; only the new lines are read
        for h in range(2):
            spans = {"data_wait": 0.0, "host_sync": 0.0, "checkpoint": 0.0}
            with open(beacon_path(tmp_path, h), "a") as f:
                for s in (4, 5):
                    f.write(json.dumps({
                        "host": h, "step": s,
                        "t_mono": 1000.0 + 7.77 * h + s * SIM_WINDOW,
                        "t_wall": SIM_T0 + s * SIM_WINDOW,
                        "metrics": {"loss": 1.0}, "spans": spans}) + "\n")
        s2 = agg.refresh()
        assert s2["hosts"]["0"]["last_step"] == 5
        assert len(s2["windows"]) > n_windows


# ---------------------------------------------------------------------------
# the alert engine
# ---------------------------------------------------------------------------


class TestAlertEngine:
    def _engine(self, *rules, sink=None):
        return AlertEngine(parse_alerts(list(rules)),
                           write_run_summary=sink)

    def test_threshold_fires(self):
        eng = self._engine({"metric": "loss", "threshold": 5.0})
        assert eng.observe(1, {"loss": 4.0}) == []
        (f,) = eng.observe(2, {"loss": 6.0})
        assert f.rule == "loss_threshold" and f.action == "log"
        assert f.value == 6.0 and "threshold" in f.message

    def test_below_fires(self):
        eng = self._engine({"metric": "mfu", "below": 0.3})
        assert eng.observe(1, {"mfu": 0.5}) == []
        (f,) = eng.observe(2, {"mfu": 0.2})
        assert "floor" in f.message

    def test_window_mean(self):
        eng = self._engine({"metric": "loss", "threshold": 5.0, "window": 3})
        # one spike in a 3-window mean must NOT fire (6+1+1)/3 = 2.67
        assert eng.observe(1, {"loss": 6.0}) == []  # window not full yet
        assert eng.observe(2, {"loss": 1.0}) == []
        assert eng.observe(3, {"loss": 1.0}) == []
        assert eng.observe(4, {"loss": 9.0}) == []  # mean 3.67
        (f,) = eng.observe(5, {"loss": 9.0})  # mean 6.33
        assert "mean of last 3" in f.message

    def test_rel_drop_vs_running_peak(self):
        eng = self._engine({"metric": "mfu", "rel_drop": 0.2})
        assert eng.observe(1, {"mfu": 0.50}) == []  # establishes the peak
        assert eng.observe(2, {"mfu": 0.45}) == []  # -10%: inside band
        (f,) = eng.observe(3, {"mfu": 0.35})        # -30%: fires
        assert "running peak 0.5" in f.message
        # the collapsed value must NOT ratchet the peak down: recovery to
        # 0.45 clears, a second collapse re-fires against the SAME peak
        assert eng.observe(4, {"mfu": 0.45}) == []
        (f2,) = eng.observe(5, {"mfu": 0.30})
        assert "0.5" in f2.message

    def test_rel_rise_vs_running_minimum(self):
        eng = self._engine({"metric": "tensorstats/pre/embed/subnormal_frac",
                            "rel_rise": 0.5})
        m = "tensorstats/pre/embed/subnormal_frac"
        assert eng.observe(1, {m: 0.10}) == []  # establishes the trough
        assert eng.observe(2, {m: 0.13}) == []  # +30%: inside band
        (f,) = eng.observe(3, {m: 0.20})        # +100%: fires
        assert "running minimum 0.1" in f.message
        # the spiked value must NOT ratchet the trough up: recovery to 0.13
        # clears, a second spike re-fires against the SAME trough
        assert eng.observe(4, {m: 0.13}) == []
        (f2,) = eng.observe(5, {m: 0.20})
        assert "0.1" in f2.message
        # a clean window BELOW the trough advances it down: 0.05 becomes the
        # new floor, so 0.08 (+60%) now fires where it never would before
        assert eng.observe(6, {m: 0.05}) == []
        (f3,) = eng.observe(7, {m: 0.08})
        assert "0.05" in f3.message

    def test_rel_rise_never_fires_from_zero_trough(self):
        # relative rise from a 0.0 trough is undefined (mirrors rel_drop's
        # non-positive-peak guard): the rule stays silent forever
        eng = self._engine({"metric": "x", "rel_rise": 0.5})
        assert eng.observe(1, {"x": 0.0}) == []
        assert eng.observe(2, {"x": 1e9}) == []

    def test_edge_triggered_no_refire_while_active(self):
        eng = self._engine({"metric": "loss", "threshold": 5.0})
        assert len(eng.observe(1, {"loss": 9.0})) == 1
        assert eng.observe(2, {"loss": 9.0}) == []  # still in violation
        assert eng.observe(3, {"loss": 1.0}) == []  # clears
        assert len(eng.observe(4, {"loss": 9.0})) == 1  # re-arms

    def test_span_prefix_fallback(self):
        eng = self._engine({"metric": "data_wait", "threshold": 1.0})
        (f,) = eng.observe(1, {"time/data_wait": 2.0})
        assert f.metric == "data_wait"

    def test_missing_and_nan_metrics_skipped(self):
        eng = self._engine({"metric": "mfu", "below": 0.3})
        assert eng.observe(1, {"loss": 1.0}) == []
        assert eng.observe(2, {"mfu": float("nan")}) == []

    def test_trail_written_and_capped(self):
        writes = []
        eng = self._engine({"metric": "loss", "threshold": 5.0},
                           sink=lambda s: writes.append(s))
        for step in range(1, 60):
            eng.observe(2 * step, {"loss": 9.0})
            eng.observe(2 * step + 1, {"loss": 1.0})  # clear -> re-arm
        from neuronx_distributed_training_tpu.telemetry.alerts import (
            MAX_FIRINGS_PER_RULE,
        )

        assert len(eng.firings) == MAX_FIRINGS_PER_RULE
        assert writes and writes[-1] == {"alerts": eng.firings}

    def test_multiple_rules_independent(self):
        eng = self._engine({"metric": "loss", "threshold": 5.0},
                           {"metric": "mfu", "below": 0.3, "action": "halt"})
        fires = eng.observe(1, {"loss": 9.0, "mfu": 0.1})
        assert {f.action for f in fires} == {"log", "halt"}


# ---------------------------------------------------------------------------
# atomic summary writes (satellite)
# ---------------------------------------------------------------------------


class TestAtomicSummaries:
    def test_unserializable_section_leaves_file_intact(self, tmp_path):
        from neuronx_distributed_training_tpu.trainer.exp_manager import (
            ExpManager,
        )

        exp = ExpManager(exp_dir=tmp_path, name="t",
                         create_tensorboard_logger=False, log_files=False)
        exp.write_run_summary({"good": 1})
        before = (exp.log_dir / "run_summary.json").read_text()
        with pytest.raises(TypeError):
            exp.write_run_summary({"bad": object()})
        # the old document is byte-identical — pre-fix this truncated it
        assert (exp.log_dir / "run_summary.json").read_text() == before
        exp.close()

    def test_kill_mid_write_leaves_valid_json(self, tmp_path, monkeypatch):
        from neuronx_distributed_training_tpu.utils import io as io_mod

        target = tmp_path / "run_summary.json"
        io_mod.atomic_write_json(target, {"step": 1})
        # simulate SIGKILL between temp write and rename: the temp file is
        # fully written but the rename never happens
        real_replace = os.replace

        def killed(src, dst):
            raise KeyboardInterrupt("SIGKILL stand-in")

        monkeypatch.setattr(os, "replace", killed)
        with pytest.raises(KeyboardInterrupt):
            io_mod.atomic_write_json(target, {"step": 2})
        monkeypatch.setattr(os, "replace", real_replace)
        assert json.loads(target.read_text()) == {"step": 1}
        # and a leftover temp file never shadows the real document
        assert json.loads(target.read_text())["step"] == 1

    def test_fleet_summary_write_atomic(self, tmp_path):
        from neuronx_distributed_training_tpu.telemetry.fleet import (
            write_fleet_summary,
        )

        p = tmp_path / "fleet_summary.json"
        write_fleet_summary({"n_hosts": 2}, p)
        assert json.loads(p.read_text())["n_hosts"] == 2
        assert not list(tmp_path.glob("*.tmp.*"))


# ---------------------------------------------------------------------------
# non-scalar sink fix (satellite)
# ---------------------------------------------------------------------------


class TestNonScalarSinks:
    def _exp(self, tmp_path):
        from neuronx_distributed_training_tpu.trainer.exp_manager import (
            ExpManager,
        )

        exp = ExpManager(exp_dir=tmp_path, name="t", log_every_n_steps=1,
                         create_tensorboard_logger=False, log_files=False)

        class StubTB:
            def __init__(self):
                self.scalars = []

            def add_scalars(self, step, flat):
                assert all(isinstance(v, float) for v in flat.values())
                self.scalars += [(k, v, step) for k, v in flat.items()]

            def close(self):
                pass

        class StubWandb:
            def __init__(self):
                self.logged = []

            def log(self, flat, step=None):
                assert all(isinstance(v, float) for v in flat.values())
                self.logged.append((dict(flat), step))

            def finish(self):
                pass

        exp._tb, exp._wandb = StubTB(), StubWandb()
        return exp

    def test_nonscalar_dropped_with_one_warning(self, tmp_path, caplog):
        exp = self._exp(tmp_path)
        bad = np.array([1.0, 2.0, 3.0])
        with caplog.at_level("WARNING"):
            exp.log_metrics(1, {"loss": 2.0, "per_layer_norms": bad})
            exp.log_metrics(2, {"loss": 1.5, "per_layer_norms": bad})
        warns = [r for r in caplog.records
                 if "per_layer_norms" in r.getMessage()]
        assert len(warns) == 1  # once, naming the key
        assert "shape (3,)" in warns[0].getMessage()
        # both sinks saw the scalar and never the array
        assert [k for k, _, _ in exp._tb.scalars] == ["loss", "loss"]
        assert all("per_layer_norms" not in f for f, _ in exp._wandb.logged)
        exp.close()

    def test_size_one_array_coerced(self, tmp_path, caplog):
        exp = self._exp(tmp_path)
        with caplog.at_level("WARNING"):
            exp.log_metrics(1, {"loss": np.array([3.25]),
                                "lr": np.float32(0.5)})
        assert not [r for r in caplog.records if "dropping" in r.getMessage()]
        assert ("loss", 3.25, 1) in exp._tb.scalars
        exp.close()


# ---------------------------------------------------------------------------
# batch stats (satellite)
# ---------------------------------------------------------------------------


class TestBatchStats:
    def test_token_stats_with_pad_id(self):
        from neuronx_distributed_training_tpu.data.loader import (
            batch_token_stats,
        )

        ids = np.array([[5, 6, 7, 0, 0, 0, 0, 0],
                        [5, 6, 7, 8, 9, 10, 11, 12]], dtype=np.int32)
        st = batch_token_stats({"input_ids": ids}, pad_id=0)
        assert st["data/padding_fraction"] == pytest.approx(5 / 16)
        assert st["data/seq_len_min"] == 3.0
        assert st["data/seq_len_max"] == 8.0
        assert st["data/seq_len_mean"] == pytest.approx(5.5)
        assert st["data/packing_efficiency"] == pytest.approx(5.5 / 8)

    def test_token_stats_from_loss_mask(self):
        from neuronx_distributed_training_tpu.data.loader import (
            batch_token_stats,
        )

        ids = np.ones((2, 4), dtype=np.int32)
        mask = np.array([[1, 1, 0, 0], [1, 1, 1, 1]], dtype=np.float32)
        st = batch_token_stats({"input_ids": ids, "loss_mask": mask})
        assert st["data/padding_fraction"] == pytest.approx(0.25)
        assert st["data/seq_len_p50"] == pytest.approx(3.0)

    def test_accumulator_drains_means(self):
        from neuronx_distributed_training_tpu.data.loader import BatchStats

        bs = BatchStats(pad_id=0)
        bs.update({"input_ids": np.array([[1, 2, 0, 0]])})
        bs.update({"input_ids": np.array([[1, 2, 3, 4]])})
        out = bs.drain()
        assert out["data/padding_fraction"] == pytest.approx(0.25)
        assert out["data/seq_len_min"] == 2.0  # min survives the window
        assert out["data/seq_len_max"] == 4.0
        assert bs.drain() == {}  # drained


# ---------------------------------------------------------------------------
# live fit() integration
# ---------------------------------------------------------------------------


def _fleet_cfg(tmp_path, **over):
    cfg = {
        "name": "fleet",
        "trainer": {"max_steps": 6, "log_every_n_steps": 2},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False,
                        "telemetry": {
                            "batch_stats": True,
                            "fleet": {"enabled": True,
                                      "stale_after_seconds": 120.0},
                        }},
        "distributed_strategy": {"tensor_model_parallel_size": 1},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        "model": {"vocab_size": 128, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    }
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k] = {**cfg[k], **v}
        else:
            cfg[k] = v
    return load_config(cfg)


@pytest.fixture(scope="module")
def fleet_run(tmp_path_factory, devices8):
    """One tiny fit() with fleet + batch_stats + a log-action alert on."""
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    tmp_path = tmp_path_factory.mktemp("fleet_run")
    cfg = _fleet_cfg(
        tmp_path,
        exp_manager={"exp_dir": str(tmp_path / "exp"),
                     "create_tensorboard_logger": False, "log_files": False,
                     "telemetry": {
                         "batch_stats": True,
                         "fleet": {"enabled": True,
                                   "stale_after_seconds": 120.0},
                         "alerts": [{"metric": "loss", "threshold": 1e9,
                                     "action": "log", "name": "never"}],
                     }})
    t = Trainer.from_config(cfg, enable_checkpointing=False)
    t.fit()
    d = Path(str(t.exp.log_dir))
    return t, d


class TestFleetLive:
    def test_beacons_written_per_boundary(self, fleet_run):
        t, d = fleet_run
        recs = [json.loads(l) for l in
                (d / "fleet" / "host_0.jsonl").read_text().splitlines()]
        steps = [r["step"] for r in recs if not r.get("closing")]
        assert steps == [2, 4, 6]  # every boundary, nothing between
        assert recs[-1]["closing"] is True  # clean close, no exception
        assert all("last_exception" not in r for r in recs)
        # beacons carry the fetched metrics + span snapshot, incl. data/
        assert recs[0]["metrics"]["loss"] > 0
        assert "data/padding_fraction" in recs[0]["metrics"]
        assert "data_wait" in recs[0]["spans"]

    def test_fleet_summary_and_run_summary(self, fleet_run):
        t, d = fleet_run
        fs = json.loads((d / "fleet_summary.json").read_text())
        assert fs["n_hosts"] == 1
        assert fs["hosts"]["0"]["closed"] is True
        assert fs["quiet_hosts"] == []
        rs = json.loads((d / "run_summary.json").read_text())
        assert rs["fleet"]["n_hosts"] == 1
        assert rs["fleet"]["summary_path"].endswith("fleet_summary.json")

    def test_batch_stats_in_metric_stream(self, fleet_run):
        t, d = fleet_run
        recs = [json.loads(l) for l in
                (d / "metrics.jsonl").read_text().splitlines()]
        last = [r for r in recs if "step_time" in r][-1]
        assert last["data/padding_fraction"] == 0.0  # synthetic: unpadded
        assert last["data/packing_efficiency"] == 1.0
        assert last["data/seq_len_max"] == 32.0

    def test_aot_once_with_fleet_enabled(self, fleet_run):
        t, _ = fleet_run
        # census swapped in the AOT executable; fleet/alerts added no
        # recompile (the retrace detector would also have logged)
        assert not hasattr(t.train_step, "lower")

    def test_alert_log_action_does_not_stop(self, fleet_run):
        t, d = fleet_run
        assert t.step == 6  # never-firing log rule: full run
        rs = json.loads((d / "run_summary.json").read_text())
        assert "alerts" not in rs  # threshold 1e9 never fired


class TestAlertHaltDrill:
    def test_data_wait_halt_lands_in_run_summary(self, tmp_path, devices8):
        """The ISSUE's acceptance drill: an alert on data_wait with
        action: halt stops the run gracefully and the reason lands in
        run_summary.json (elastic.stop_reason + the alerts trail)."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _fleet_cfg(
            tmp_path,
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "telemetry": {
                             "fleet": {"enabled": True},
                             "alerts": [{"metric": "data_wait",
                                         "threshold": 1e-12,
                                         "action": "halt", "name": "dw"}],
                         }})
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        t.fit()
        assert t.step == 2  # halted at the first boundary
        rs = json.loads(
            (Path(str(t.exp.log_dir)) / "run_summary.json").read_text())
        assert rs["elastic"]["stop_reason"].startswith("alert dw:")
        assert "data_wait" in rs["elastic"]["stop_reason"]
        (fire,) = rs["alerts"]
        assert fire["rule"] == "dw" and fire["action"] == "halt"
        assert fire["step"] == 2

    def test_alert_dump_writes_flight_recorder_bundle(self, tmp_path,
                                                      devices8):
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _fleet_cfg(
            tmp_path,
            trainer={"max_steps": 4, "log_every_n_steps": 2},
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "telemetry": {
                             "alerts": [{"metric": "loss", "threshold": 0.0,
                                         "action": "dump", "name": "dl"}],
                         }})
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        t.fit()
        d = Path(str(t.exp.log_dir))
        bundles = sorted(p.name for p in d.glob("alert_*"))
        assert bundles == ["alert_00000002"]  # edge-triggered: ONE bundle
        payload = json.loads((d / bundles[0] / "anomaly.json").read_text())
        assert payload["kind"] == "alert"
        assert payload["alert"]["rule"] == "dl"
        rs = json.loads((d / "run_summary.json").read_text())
        assert any(a["bundle"] == "alert_00000002"
                   for a in rs["anomalies"])

    def test_dispatch_ahead_contract_with_fleet_and_alerts(self, tmp_path,
                                                           devices8):
        """Fleet + alerts enabled must add ZERO host syncs between logging
        boundaries — the same instrumented-step proof the telemetry layer
        pins, with the new knobs on."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _fleet_cfg(
            tmp_path,
            trainer={"max_steps": 6, "log_every_n_steps": 3},
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "telemetry": {
                             "batch_stats": True,
                             "fleet": {"enabled": True},
                             "alerts": [{"metric": "loss",
                                         "threshold": 1e9}],
                         }})
        t = Trainer.from_config(cfg, enable_checkpointing=False)

        conversions: list[int] = []

        class _Scalar:
            def __init__(self, step):
                self.step = step

            def __float__(self):
                conversions.append(self.step)
                return 1.0

        real_params, real_opt = t.params, t.opt_state

        def fake_step(params, opt_state, batch, key):
            return real_params, real_opt, {"loss": _Scalar(t.step),
                                           "grad_norm": _Scalar(t.step)}

        t.train_step = fake_step
        t.fit()
        assert conversions, "boundaries must fetch metrics"
        assert set(conversions) == {2, 5}, conversions


class TestMultiIncarnation:
    def test_beacons_extend_across_kill_and_resume(self, tmp_path, devices8):
        """The elastic drill's process machinery at fleet level: incarnation
        1 is killed mid-run by the fault injector (its beacon stream ends
        with last_exception — a DYING host leaves a valid file), incarnation
        2 resumes into the SAME version dir and extends the stream; the
        aggregator sees one host whose record covers both lives."""
        from neuronx_distributed_training_tpu.trainer.elastic import (
            FaultInjector,
            SimulatedPreemption,
        )
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        over = dict(
            trainer={"max_steps": 6, "log_every_n_steps": 1},
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "resume_if_exists": True,
                         "checkpoint_callback_params": {
                             "every_n_train_steps": 2, "save_top_k": 2},
                         "telemetry": {
                             "fleet": {"enabled": True},
                         }},
        )
        cfg = _fleet_cfg(tmp_path, **over)
        t1 = Trainer.from_config(cfg)
        t1.fault_injector = FaultInjector(at_step=3, mode="kill",
                                          phase="step")
        with pytest.raises(SimulatedPreemption):
            t1.fit()
        d = Path(str(t1.exp.log_dir))
        recs = [json.loads(l) for l in
                (d / "fleet" / "host_0.jsonl").read_text().splitlines()]
        assert recs[-1].get("last_exception", "").startswith(
            "SimulatedPreemption")

        t2 = Trainer.from_config(cfg)
        t2.fit()
        assert Path(str(t2.exp.log_dir)) == d  # same version dir
        recs2 = [json.loads(l) for l in
                 (d / "fleet" / "host_0.jsonl").read_text().splitlines()]
        assert len(recs2) > len(recs)  # the stream EXTENDED
        assert recs2[-1].get("closing") is True  # clean second life
        fs = json.loads((d / "fleet_summary.json").read_text())
        assert fs["n_hosts"] == 1
        assert fs["hosts"]["0"]["last_step"] == 6
        assert fs["hosts"]["0"]["beacons"] == len(recs2)


# ---------------------------------------------------------------------------
# in-loop quiet-host detection (seeded second host)
# ---------------------------------------------------------------------------


class TestInLoopFleetStall:
    def test_quiet_host_dumps_fleet_stall_bundle(self, tmp_path, devices8):
        """Rank 0's boundary aggregation must notice a host that stopped
        beaconing and dump ONE fleet_stall bundle through the flight
        recorder.  The quiet host is seeded: a second beacon stream whose
        last record is minutes old."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _fleet_cfg(
            tmp_path,
            trainer={"max_steps": 6, "log_every_n_steps": 2},
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "telemetry": {
                             "fleet": {"enabled": True,
                                       "stale_after_seconds": 60.0},
                             # a dump-capable monitor must exist for the
                             # stall bundle: any dump-action rule arms one
                             "alerts": [{"metric": "loss",
                                         "threshold": 1e9,
                                         "action": "dump"}],
                         }})
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        d = Path(str(t.exp.log_dir))
        # seed host 7: last beacon 10 minutes in the past, never closed
        (d / "fleet").mkdir(parents=True, exist_ok=True)
        (d / "fleet" / "host_7.jsonl").write_text(json.dumps({
            "host": 7, "step": 1, "t_mono": 1.0,
            "t_wall": time.time() - 600.0, "metrics": {"loss": 2.0},
        }) + "\n")
        t.fit()
        fs = json.loads((d / "fleet_summary.json").read_text())
        assert [q["host"] for q in fs["quiet_hosts"]] == [7]
        stalls = [f for f in fs["findings"] if f["kind"] == "fleet_stall"]
        assert len(stalls) == 1 and stalls[0]["host"] == 7
        bundles = sorted(p.name for p in d.glob("fleet_stall_*"))
        assert len(bundles) == 1  # once per host, not per boundary
        payload = json.loads(
            (d / bundles[0] / "anomaly.json").read_text())
        assert payload["kind"] == "fleet_stall"
        assert payload["quiet_hosts"][0]["host"] == 7


# ---------------------------------------------------------------------------
# CLIs: fleet_monitor + metrics_report --follow
# ---------------------------------------------------------------------------


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    import sys

    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class TestFleetMonitorCLI:
    def test_fixture_committed_and_current(self):
        """The committed simulated-fleet fixture must match the generator
        (regen with `python tests/test_fleet.py --regen-fixture`)."""
        import tempfile

        assert FIXTURE.is_dir(), "tests/data/fleet_fixture missing"
        with tempfile.TemporaryDirectory() as td:
            write_sim_fleet(Path(td), n_hosts=5, n_steps=8, straggler=2,
                            cause="data_stall", quiet_host=3, quiet_after=4,
                            die_host=4, die_after=6)
            for p in sorted(Path(td).glob("*.jsonl")):
                assert (FIXTURE / p.name).read_text() == p.read_text(), p.name

    def test_json_last_line_contract(self, capsys):
        fm = _load_tool("fleet_monitor")
        rc = fm.main([str(FIXTURE), "--json", "-"])
        out = capsys.readouterr().out
        payload = json.loads(out.strip().splitlines()[-1])
        assert payload["n_hosts"] == 5
        assert payload["straggler"]["host"] == 2
        assert payload["straggler"]["cause"] == "data_stall"
        assert [q["host"] for q in payload["quiet_hosts"]] == [3]
        kinds = {f["kind"] for f in payload["findings"]}
        assert kinds == {"fleet_stall", "host_died"}
        assert rc == 1  # findings -> nonzero, like ckpt_verify

    def test_human_render(self, capsys):
        fm = _load_tool("fleet_monitor")
        fm.main([str(FIXTURE)])
        out = capsys.readouterr().out
        assert "straggler: host 2" in out
        assert "data_stall" in out
        assert "QUIET" in out
        assert "fleet goodput" in out
        assert "[host_died]" in out

    def test_run_dir_form_and_write(self, tmp_path, capsys):
        fm = _load_tool("fleet_monitor")
        write_sim_fleet(tmp_path / "fleet", n_hosts=2, n_steps=3,
                        straggler=None)
        rc = fm.main([str(tmp_path), "--write"])
        assert rc == 0  # no findings
        fs = json.loads((tmp_path / "fleet_summary.json").read_text())
        assert fs["n_hosts"] == 2

    def test_summary_file_form(self, tmp_path, capsys):
        fm = _load_tool("fleet_monitor")
        p = tmp_path / "fleet_summary.json"
        p.write_text(json.dumps({"n_hosts": 3, "hosts": {}, "windows": [],
                                 "findings": []}))
        assert fm.main([str(p)]) == 0
        assert "3 hosts" in capsys.readouterr().out

    def test_missing_input(self, tmp_path):
        fm = _load_tool("fleet_monitor")
        assert fm.main([str(tmp_path / "nope")]) == 2


class TestMetricsReportFollow:
    def _run_dir(self, tmp_path):
        with open(tmp_path / "metrics.jsonl", "w") as f:
            for s in (2, 4):
                f.write(json.dumps({"step": s, "loss": 7.0 - s}) + "\n")
        with open(tmp_path / "run_summary.json", "w") as f:
            json.dump({"alerts": [{"step": 4, "rule": "dw",
                                   "action": "halt", "metric": "data_wait",
                                   "message": "data_wait too high"}]}, f)
        write_sim_fleet(tmp_path / "fleet", n_hosts=2, n_steps=3,
                        straggler=1, cause="compute_slow")
        fm = _load_tool("fleet_monitor")
        fm.main([str(tmp_path), "--write"])
        return tmp_path

    def test_follow_smoke(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        d = self._run_dir(tmp_path)
        rc = mr.main([str(d), "--follow", "--interval", "0.01",
                      "--refreshes", "2"])
        assert rc == 0
        out = capsys.readouterr().out
        assert out.count("refresh 1") == 1 and out.count("refresh 2") == 1
        assert "beacons (age" in out
        assert "host_0" in out and "host_1" in out

    def test_fleet_and_alert_sections_render(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        d = self._run_dir(tmp_path)
        assert mr.main([str(d)]) == 0
        out = capsys.readouterr().out
        assert "fleet (2 hosts" in out
        assert "straggler" in out
        assert "alerts (1 firing" in out
        assert "data_wait too high" in out

    def test_no_fleet_dir_sections_absent(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        with open(tmp_path / "metrics.jsonl", "w") as f:
            f.write(json.dumps({"step": 2, "loss": 1.0}) + "\n")
        assert mr.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "beacons" not in out and "fleet (" not in out


if __name__ == "__main__":
    import sys

    if "--regen-fixture" in sys.argv:
        regen_fixture()
        print(f"regenerated {FIXTURE}")
    else:
        print(__doc__)
