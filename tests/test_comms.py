"""Interconnect observatory (telemetry.comms): measured collective bandwidth.

Covers the bus-bandwidth conventions against hand numbers, the in-loop
achieved-bandwidth join (cost-model byte volumes x traced wire seconds),
the per-axis bandwidth/latency fit recovering an exactly-planted plane, the
seeded-slow-device skew detector, the worked degraded-link alert rule
firing through the real alert engine, the committed hand-computable
``comms_summary`` fixture (byte-stable ratchet), the live CPU-mesh sweep on
virtual devices, the planner calibration round-trip (fixture AND
live-captured summary), quant-readiness savings provenance, fleet
beacon/spread wiring, and the CLI smokes (tools/comms_bench.py, tools/comms_report.py).

Run ``python tests/test_comms.py --regen-fixture`` to regenerate the
committed fixture after changing ``build_fixture()`` — the ratchet test
diffs bytes, so drift is loud.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

from neuronx_distributed_training_tpu.telemetry import comms

FIXTURE = Path(__file__).parent / "data" / "comms_summary_fixture.json"


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the committed fixture: two axes planted EXACTLY on t = B/bw + hops x lat
# planes, so the fit must recover the planted parameters to the digit
# ---------------------------------------------------------------------------

PRIOR_BW = 2e9  # the topology prior the fixture bench "saw" (cpu row)
PRIOR_LAT = 2e-5
#: device 3 is the seeded slow device: 0.0025s vs a 0.00105s fleet median
#: (ratio 2.381 > the 1.5x threshold); devices 0-2 are healthy
SKEW = {"0": 0.001, "1": 0.001, "2": 0.0011, "3": 0.0025}


def _plane_rows(points):
    """Sweep rows lying exactly on a planted (bw, lat) plane, shaped and
    rounded like ``run_comms_sweep`` emits them."""
    rows = []
    for kind, payload, bw, lat in points:
        n = 2
        bb = comms.bus_bytes(kind, payload, n)
        hops = comms.ring_hops(kind, n)
        t = bb / bw + hops * lat
        rows.append({
            "collective": kind, "payload_bytes": int(payload),
            "bus_bytes": round(bb, 1), "hops": hops,
            "seconds_median": round(t, 9), "seconds_min": round(t, 9),
            "reps": 3, "bus_gbps": round(bb / t / 1e9, 6),
        })
    return rows


def build_fixture() -> dict:
    # dp: 1 GB/s + 100us/hop (ratio 0.5 vs the 2 GB/s prior);
    # pp: 0.5 GB/s + 200us/hop (ratio 0.25)
    axis_results = {
        "dp": {"mesh_axis": "data", "size": 2, "sweep": _plane_rows([
            ("all-gather", 1 << 20, 1e9, 1e-4),
            ("all-gather", 4 << 20, 1e9, 1e-4),
            ("all-reduce", 1 << 20, 1e9, 1e-4),
        ])},
        "pp": {"mesh_axis": "pipe", "size": 2, "sweep": _plane_rows([
            ("collective-permute", 1 << 20, 5e8, 2e-4),
            ("collective-permute", 4 << 20, 5e8, 2e-4),
        ])},
    }
    return comms.build_comms_summary(
        axis_results, topology_name="cpu",
        prior_bandwidth_bytes=PRIOR_BW, prior_latency_seconds=PRIOR_LAT,
        device_skew=SKEW)


def build_fixture_bytes() -> bytes:
    # the exact serialization write_comms_summary uses
    return (json.dumps(build_fixture(), indent=1, sort_keys=True)
            + "\n").encode()


@pytest.fixture(scope="module")
def fixture_doc():
    return json.loads(FIXTURE.read_text())


# ---------------------------------------------------------------------------
# bus-bandwidth conventions (hand numbers)
# ---------------------------------------------------------------------------


class TestBusMath:
    def test_bus_bytes_ring_factors(self):
        # NCCL-tests vocabulary over n=4 ranks, 1000-byte payload
        assert comms.bus_bytes("all-reduce", 1000, 4) == 1500.0  # 2B(n-1)/n
        assert comms.bus_bytes("all-gather", 1000, 4) == 750.0  # B(n-1)/n
        assert comms.bus_bytes("reduce-scatter", 1000, 4) == 750.0
        assert comms.bus_bytes("all-to-all", 1000, 4) == 750.0
        assert comms.bus_bytes("collective-permute", 1000, 4) == 1000.0
        assert comms.bus_bytes("all-reduce", 1000, 1) == 0.0
        assert comms.bus_bytes("all-reduce", 0, 4) == 0.0

    def test_ring_hops(self):
        assert comms.ring_hops("all-reduce", 4) == 6  # 2(n-1)
        assert comms.ring_hops("all-gather", 4) == 3
        assert comms.ring_hops("reduce-scatter", 4) == 3
        assert comms.ring_hops("all-to-all", 4) == 3
        assert comms.ring_hops("collective-permute", 4) == 1
        assert comms.ring_hops("all-gather", 1) == 0

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown collective kind"):
            comms.bus_bytes("all-scatter", 1000, 4)
        with pytest.raises(ValueError, match="unknown collective kind"):
            comms.ring_hops("all-scatter", 4)

    def test_kinds_match_debug_vocabulary(self):
        # COMMS_KINDS is duplicated so the module imports without jax —
        # it must never drift from the tracer's vocabulary
        from neuronx_distributed_training_tpu.utils.debug import (
            COLLECTIVE_KINDS,
        )

        assert tuple(comms.COMMS_KINDS) == tuple(COLLECTIVE_KINDS)

    def test_class_bus_bytes_per_step(self):
        per_class = comms.class_bus_bytes_per_step(
            {"tp": {"all-gather": 1000.0, "reduce-scatter": 1000.0},
             "dp": {"all-reduce": 2000.0},
             "pp": {"collective-permute": 500.0}},
            {"tp": 4, "dp": 2, "pp": 1})
        # pp degenerate (n=1) contributes nothing; the rest fold through
        # the ring factors
        assert per_class == {"all-gather": 750.0, "reduce-scatter": 750.0,
                             "all-reduce": 2000.0}

    def test_axes_summed_per_class(self):
        per_class = comms.class_bus_bytes_per_step(
            {"tp": {"all-gather": 1000.0}, "dp": {"all-gather": 1000.0}},
            {"tp": 2, "dp": 2})
        assert per_class == {"all-gather": 1000.0}  # 500 + 500


# ---------------------------------------------------------------------------
# the in-loop join (comms_section) with hand numbers
# ---------------------------------------------------------------------------


def _facts_block():
    return {"byte_volumes": {"tp": {"all-gather": float(1 << 20)}},
            "axis_sizes": {"tp": 2},
            "peak_bandwidth_bytes": 1e9, "topology": "cpu"}


class TestCommsSection:
    def test_hand_computed_join(self):
        # bus bytes/step = 1MiB/2 = 524288; wire = 2ms over 2 steps = 1ms
        # per step -> 524288000 B/s achieved = 0.524288 GB/s; efficiency
        # against the 1 GB/s peak is the same number
        section = comms.comms_section(
            _facts_block(),
            {"all-gather": {"wire_seconds": 0.002, "count": 10}},
            window_steps=2)
        e = section["classes"]["all-gather"]
        assert e["bus_bytes_per_step"] == 524288.0
        assert e["wire_seconds_per_step"] == pytest.approx(0.001)
        assert e["achieved_gbps"] == pytest.approx(0.524288)
        assert e["efficiency"] == pytest.approx(0.524288)
        assert e["count"] == 10
        assert section["window_steps"] == 2
        assert section["peak_bandwidth_gbps"] == 1.0
        assert section["topology"] == "cpu"

    def test_untraced_class_is_skipped(self):
        # volumes name all-gather but the trace saw only all-reduce: the
        # join never invents a wire time
        assert comms.comms_section(
            _facts_block(),
            {"all-reduce": {"wire_seconds": 0.1}}, window_steps=2) is None

    def test_nothing_to_say_returns_none(self):
        assert comms.comms_section({}, {}, window_steps=2) is None
        assert comms.comms_section(_facts_block(), {}, window_steps=0) is None
        assert comms.comms_section(
            {"byte_volumes": {}, "axis_sizes": {}}, {"all-gather":
                {"wire_seconds": 1.0}}, window_steps=2) is None

    def test_zero_wire_seconds_skipped(self):
        assert comms.comms_section(
            _facts_block(), {"all-gather": {"wire_seconds": 0.0}},
            window_steps=2) is None

    def test_no_peak_means_no_efficiency(self):
        facts = dict(_facts_block(), peak_bandwidth_bytes=0.0)
        section = comms.comms_section(
            facts, {"all-gather": {"wire_seconds": 0.002}}, window_steps=2)
        assert "efficiency" not in section["classes"]["all-gather"]
        assert "peak_bandwidth_gbps" not in section

    def test_metrics_flattening(self):
        section = comms.comms_section(
            _facts_block(),
            {"all-gather": {"wire_seconds": 0.002, "count": 1}},
            window_steps=2)
        scalars = comms.comms_metrics(section)
        assert scalars == {
            "comms/all-gather/achieved_gbps": pytest.approx(0.524288),
            "comms/all-gather/efficiency": pytest.approx(0.524288),
        }
        assert comms.comms_metrics(None) == {}


# ---------------------------------------------------------------------------
# the worked degraded-link alert rule, through the real engine
# ---------------------------------------------------------------------------


class TestDegradedLinkRule:
    def test_rule_validates(self):
        from neuronx_distributed_training_tpu.telemetry.alerts import (
            AlertRule,
        )

        r = AlertRule.from_config(comms.degraded_link_alert_rule())
        assert r.name == "comms_degraded_link"
        assert r.metric == "comms/all-gather/achieved_gbps"
        assert r.window == 3 and r.rel_drop == 0.5 and r.action == "log"
        r = AlertRule.from_config(comms.degraded_link_alert_rule(
            kind="reduce-scatter", window=1, rel_drop=0.3, action="halt"))
        assert r.metric == "comms/reduce-scatter/achieved_gbps"
        assert r.action == "halt"

    def test_fires_on_bandwidth_collapse(self):
        from neuronx_distributed_training_tpu.telemetry.alerts import (
            AlertEngine,
            AlertRule,
        )

        eng = AlertEngine([AlertRule.from_config(
            comms.degraded_link_alert_rule(window=1))])
        # healthy window establishes the peak; a boundary with no comms
        # metric (no trace window fired) is simply skipped
        assert eng.observe(1, {"comms/all-gather/achieved_gbps": 10.0}) == []
        assert eng.observe(2, {"loss": 2.0}) == []
        fired = eng.observe(3, {"comms/all-gather/achieved_gbps": 4.0})
        assert [f.rule for f in fired] == ["comms_degraded_link"]
        assert fired[0].value == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# the per-axis fit
# ---------------------------------------------------------------------------


class TestAxisFit:
    def test_exact_recovery_of_planted_plane(self):
        # three points exactly on t = B/1e9 + hops * 1e-4: the normal
        # equations must hand back the planted parameters
        fit = comms.fit_axis_bandwidth([
            {"bus_bytes": 524288.0, "hops": 1, "seconds": 0.000624288},
            {"bus_bytes": 2097152.0, "hops": 1, "seconds": 0.002197152},
            {"bus_bytes": 1048576.0, "hops": 2, "seconds": 0.001248576},
        ])
        assert fit == {"bandwidth_bytes_per_s": 1e9,
                       "latency_seconds": 1e-4, "n_points": 3}

    def test_slope_only_fallback_on_degenerate_system(self):
        # hops all zero: the 2-parameter system is singular; the fit falls
        # back to the latency-free slope
        fit = comms.fit_axis_bandwidth(
            [{"bus_bytes": 1e6, "hops": 0, "seconds": 0.001}])
        assert fit["bandwidth_bytes_per_s"] == pytest.approx(1e9)
        assert fit["latency_seconds"] == 0.0

    def test_negative_latency_rejected(self):
        # a plane whose exact solution has lat < 0 (timing noise shape)
        # must not be reported as-is: the fit degrades to slope-only
        fit = comms.fit_axis_bandwidth([
            {"bus_bytes": 1e6, "hops": 2, "seconds": 0.0009},
            {"bus_bytes": 4e6, "hops": 1, "seconds": 0.004},
        ])
        assert fit["latency_seconds"] == 0.0
        assert fit["bandwidth_bytes_per_s"] > 0

    def test_garbage_points_skipped(self):
        assert comms.fit_axis_bandwidth([]) is None
        assert comms.fit_axis_bandwidth(
            [{"bus_bytes": -1, "hops": 0, "seconds": 0.1},
             {"hops": 1}, {"bus_bytes": 1e6, "seconds": 0}]) is None


# ---------------------------------------------------------------------------
# skew detection (seeded slow device)
# ---------------------------------------------------------------------------


class TestSkew:
    def test_seeded_slow_device_named(self):
        findings = comms.skew_findings(SKEW)
        assert len(findings) == 1
        f = findings[0]
        assert f["kind"] == "degraded_link"
        assert f["device"] == "3"
        assert f["ratio"] == pytest.approx(0.0025 / 0.00105, abs=1e-3)
        assert "device 3" in f["message"]

    def test_uniform_fleet_is_clean(self):
        assert comms.skew_findings({"0": 0.001, "1": 0.001}) == []

    def test_threshold_respected(self):
        assert comms.skew_findings(SKEW, rel_threshold=3.0) == []

    def test_single_device_says_nothing(self):
        assert comms.skew_findings({"0": 99.0}) == []
        assert comms.skew_findings({}) == []


# ---------------------------------------------------------------------------
# the committed fixture (byte-stable ratchet) + artifact round trips
# ---------------------------------------------------------------------------


class TestFixture:
    def test_fixture_committed_and_current(self):
        """Bytes-equal ratchet: drift in the builder OR the serializer is
        loud; regenerate with ``python tests/test_comms.py
        --regen-fixture``."""
        assert FIXTURE.exists(), \
            "fixture missing: python tests/test_comms.py --regen-fixture"
        assert FIXTURE.read_bytes() == build_fixture_bytes()

    def test_fit_recovers_planted_planes(self, fixture_doc):
        dp = fixture_doc["axes"]["dp"]
        assert dp["fit"] == {"bandwidth_bytes_per_s": 1e9,
                             "latency_seconds": 1e-4, "n_points": 3}
        assert dp["bandwidth_ratio"] == 0.5  # 1 GB/s vs the 2 GB/s prior
        assert dp["latency_ratio"] == 5.0
        pp = fixture_doc["axes"]["pp"]
        assert pp["fit"] == {"bandwidth_bytes_per_s": 5e8,
                             "latency_seconds": 2e-4, "n_points": 2}
        assert pp["bandwidth_ratio"] == 0.25

    def test_degraded_link_finding(self, fixture_doc):
        assert [f["device"] for f in fixture_doc["findings"]] == ["3"]
        skew = fixture_doc["device_skew"]
        assert skew["median_seconds"] == 0.00105
        assert skew["findings"] == fixture_doc["findings"]

    def test_sniff_and_load(self, fixture_doc, tmp_path):
        assert comms.is_comms_summary(fixture_doc)
        # kind marker stripped: the axes+prior pair still identifies it
        anonymous = {k: v for k, v in fixture_doc.items() if k != "kind"}
        assert comms.is_comms_summary(anonymous)
        # things that must NOT sniff as a comms summary
        assert not comms.is_comms_summary({"overlap_by_class": {}})
        assert not comms.is_comms_summary(None)
        # a run dir resolves the canonical name
        comms.write_comms_summary(fixture_doc,
                                  tmp_path / comms.COMMS_SUMMARY_NAME)
        assert comms.load_comms_summary(tmp_path) == fixture_doc
        with pytest.raises(ValueError, match="no comms summary"):
            comms.load_comms_summary(tmp_path / "nope.json")

    def test_write_is_byte_stable(self, fixture_doc, tmp_path):
        out = tmp_path / "a.json"
        comms.write_comms_summary(fixture_doc, out)
        assert out.read_bytes() == FIXTURE.read_bytes()
        first = out.read_bytes()
        comms.write_comms_summary(json.loads(out.read_text()), out)
        assert out.read_bytes() == first


# ---------------------------------------------------------------------------
# live CPU-mesh sweep (virtual devices drive the real collectives)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live_sweep(cpu_mesh):
    # dp=4 x tp=2; two kinds x two sizes keeps the compile bill small
    return comms.run_comms_sweep(
        cpu_mesh, sizes_bytes=(1 << 12, 1 << 14),
        kinds=("all-gather", "all-reduce"), warmup=1, reps=2)


@pytest.fixture(scope="module")
def live_summary(live_sweep, devices8):
    from neuronx_distributed_training_tpu.autotune.topology import (
        resolve_topology,
    )

    topo = resolve_topology(device=devices8[0])
    return comms.build_comms_summary(
        live_sweep, topology_name=topo.name,
        prior_bandwidth_bytes=topo.ici_bandwidth_bytes,
        prior_latency_seconds=topo.ici_latency_seconds,
        device_skew=comms.measure_device_skew(devices8, reps=1))


class TestLiveSweep:
    def test_axes_and_rows(self, live_sweep):
        assert set(live_sweep) == {"dp", "tp"}
        assert live_sweep["dp"]["mesh_axis"] == "data"
        assert live_sweep["dp"]["size"] == 4
        rows = live_sweep["dp"]["sweep"]
        assert {r["collective"] for r in rows} == {"all-gather",
                                                   "all-reduce"}
        for r in rows:
            assert r["seconds_median"] > 0 and r["bus_gbps"] > 0
            assert r["reps"] == 2
            assert r["hops"] == comms.ring_hops(r["collective"], 4)
            assert r["bus_bytes"] == pytest.approx(comms.bus_bytes(
                r["collective"], r["payload_bytes"], 4))

    def test_summary_fits_every_axis(self, live_summary, devices8):
        assert comms.is_comms_summary(live_summary)
        for axis in ("dp", "tp"):
            fit = live_summary["axes"][axis]["fit"]
            assert fit["bandwidth_bytes_per_s"] > 0
            assert fit["latency_seconds"] >= 0
            assert fit["n_points"] == 4
            assert live_summary["axes"][axis]["bandwidth_ratio"] > 0
        skew = live_summary["device_skew"]
        assert len(skew["per_device"]) == len(devices8)
        assert all(t > 0 for t in skew["per_device"].values())

    def test_round_trip_and_live_calibration(self, live_summary, tmp_path):
        """The satellite acceptance: a live-captured summary survives
        write -> load -> planner-calibration extraction."""
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            _COMMS_RATIO_BOUNDS,
            comms_calibration_from_summary,
        )

        out = tmp_path / comms.COMMS_SUMMARY_NAME
        comms.write_comms_summary(live_summary, out)
        cal = comms_calibration_from_summary(str(out))
        assert set(cal) == {"dp", "tp"}
        lo, hi = _COMMS_RATIO_BOUNDS
        assert all(lo <= v <= hi for v in cal.values())


# ---------------------------------------------------------------------------
# planner calibration (fixture round trip, clamping, repricing)
# ---------------------------------------------------------------------------


class TestCalibration:
    def test_ratios_from_fixture(self, fixture_doc):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            comms_calibration_from_summary,
        )

        assert comms_calibration_from_summary(fixture_doc) == {
            "dp": 0.5, "pp": 0.25}
        # also from the committed file path (the CLI's shape)
        assert comms_calibration_from_summary(str(FIXTURE)) == {
            "dp": 0.5, "pp": 0.25}

    def test_ratio_clamped(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            _COMMS_RATIO_BOUNDS,
            comms_calibration_from_summary,
        )

        doc = {"kind": "comms_summary",
               "prior": {"ici_bandwidth_bytes": 1e9,
                         "ici_latency_seconds": 0.0},
               "axes": {"tp": {"fit": {"bandwidth_bytes_per_s": 1e3,
                                       "latency_seconds": 0.0,
                                       "n_points": 2}}}}
        assert comms_calibration_from_summary(doc) == {
            "tp": _COMMS_RATIO_BOUNDS[0]}

    def test_unusable_summary_raises(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            comms_calibration_from_summary,
        )

        with pytest.raises(ValueError, match="no fitted"):
            comms_calibration_from_summary(
                {"kind": "comms_summary", "prior": {}, "axes": {}})
        with pytest.raises(ValueError, match="must be a mapping"):
            comms_calibration_from_summary(
                {"kind": "comms_summary", "axes": [1, 2]})

    def test_estimate_reprices_comms(self):
        # halved measured bandwidth must make the priced comms term grow
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            estimate_plan,
        )
        from neuronx_distributed_training_tpu.autotune.space import (
            ModelFacts,
            Plan,
        )
        from neuronx_distributed_training_tpu.autotune.topology import (
            resolve_topology,
        )
        from neuronx_distributed_training_tpu.config.loader import (
            load_config,
        )

        facts = ModelFacts.from_config(
            load_config("examples/conf/tiny_smoke_config.yaml"))
        plan = Plan(tp=2, pp=1, cp=1, ep=1, dp=4, micro_batch_size=2,
                    num_microbatches=1, remat="none", schedule="none")
        topo = resolve_topology("cpu")
        base = estimate_plan(facts, plan, topo)
        slow = estimate_plan(facts, plan, topo,
                             comms_calibration={"tp": 0.5, "dp": 0.5})
        assert slow.comms_seconds > base.comms_seconds
        assert slow.compute_seconds == base.compute_seconds

    def test_plan_config_sniffs_comms_summary(self):
        """The --calibrate-from loop: content-sniffed comms summary lands
        measured/prior ratios in the report header."""
        from neuronx_distributed_training_tpu.autotune import plan_config

        rep = plan_config("examples/conf/tiny_smoke_config.yaml", chips=8,
                          topology="cpu", audit=False, top_k=1,
                          calibration=str(FIXTURE))
        assert rep.error is None
        assert rep.comms_calibration == {"dp": 0.5, "pp": 0.25}
        text = rep.format()
        assert "comms bandwidth (measured/prior)" in text
        assert "dp=0.50" in text and "pp=0.25" in text


# ---------------------------------------------------------------------------
# quant-readiness: savings provenance (measured wire rate vs static)
# ---------------------------------------------------------------------------


class TestQuantSavingsSource:
    def test_measured_wire_rate_wins_when_comms_present(self):
        from neuronx_distributed_training_tpu.telemetry.quant_readiness import (
            build_report,
            bytes_saved_fraction,
        )

        sf = bytes_saved_fraction(512, 4.0)
        report = build_report(
            None, block_sizes=(512,),
            byte_volumes={"all-gather": 1000.0},
            overlap_by_class={"all-gather": {"exposed_seconds": 0.5,
                                             "wire_seconds": 1.0}},
            comms={"classes": {"all-gather": {"achieved_gbps": 2.0,
                                              "bus_bytes_per_step": 2e6}}})
        e = report["classes"]["all-gather"]
        assert e["savings_source"] == "measured_wire_rate"
        assert e["predicted_seconds_saved"] == round(2e6 * sf / 2e9, 9)

    def test_static_fallback_names_itself(self):
        from neuronx_distributed_training_tpu.telemetry.quant_readiness import (
            build_report,
            bytes_saved_fraction,
        )

        sf = bytes_saved_fraction(512, 4.0)
        report = build_report(
            None, block_sizes=(512,),
            byte_volumes={"all-gather": 1000.0},
            overlap_by_class={"all-gather": {"exposed_seconds": 0.5,
                                             "wire_seconds": 1.0}})
        e = report["classes"]["all-gather"]
        assert e["savings_source"] == "static_exposed_fraction"
        assert e["predicted_seconds_saved"] == pytest.approx(0.5 * sf)


# ---------------------------------------------------------------------------
# fleet plane: beacons carry comms/*, the spread survives later beacons
# ---------------------------------------------------------------------------


class TestFleetComms:
    def test_beacon_picks_comms_metrics(self, tmp_path):
        from neuronx_distributed_training_tpu.telemetry.fleet import (
            FleetBeacon,
            beacon_path,
        )

        b = FleetBeacon(tmp_path, host=1)
        b.emit(10, {"comms/all-gather/achieved_gbps": 0.5,
                    "comms/all-gather/efficiency": 0.25,
                    "grad_norm": 1.0})
        b.close()
        rec = json.loads(
            beacon_path(tmp_path, 1).read_text().splitlines()[0])
        assert rec["metrics"]["comms/all-gather/achieved_gbps"] == 0.5
        assert rec["metrics"]["comms/all-gather/efficiency"] == 0.25
        assert "grad_norm" not in rec["metrics"]

    def test_spread_sticky_across_later_beacons(self, tmp_path):
        """The join fires once per trace window; regular beacons after it
        must not erase the per-host number before anyone reads the
        spread — that is how the aggregator names a degraded host."""
        from neuronx_distributed_training_tpu.telemetry.fleet import (
            FleetBeacon,
            aggregate_fleet,
        )

        for host, bw in ((0, 1.0), (1, 0.2)):
            b = FleetBeacon(tmp_path, host=host)
            b.emit(10, {"loss": 2.0,
                        "comms/all-gather/achieved_gbps": bw})
            b.emit(20, {"loss": 1.9})  # no comms metric on this boundary
            b.close()
        sp = aggregate_fleet(tmp_path)["spread"][
            "comms/all-gather/achieved_gbps"]
        assert sp["min"] == {"host": 1, "value": 0.2}
        assert sp["max"] == {"host": 0, "value": 1.0}


# ---------------------------------------------------------------------------
# CLI smokes
# ---------------------------------------------------------------------------


class TestCommsReportCLI:
    def test_renders_fixture_summary(self, tmp_path, capsys):
        mod = _load_tool("comms_report")
        assert mod.main([str(FIXTURE), "--json",
                         str(tmp_path / "r.json")]) == 0
        out = capsys.readouterr().out
        for needle in ("per-axis fit", "all-gather", "degraded",
                       "device 3"):
            assert needle in out, (needle, out)
        doc = json.loads((tmp_path / "r.json").read_text())
        assert doc["ok"] and doc["kind"] == "summary"

    def test_renders_run_dir_section(self, tmp_path, capsys):
        mod = _load_tool("comms_report")
        (tmp_path / "run_summary.json").write_text(json.dumps({
            "comms": {"classes": {"all-gather": {
                "achieved_gbps": 0.5, "efficiency": 0.25,
                "bus_bytes_per_step": 1000.0,
                "wire_seconds_per_step": 2e-6, "count": 4}},
                "window_steps": 2, "peak_bandwidth_gbps": 2.0,
                "topology": "cpu"}}))
        assert mod.main([str(tmp_path), "--json", "-"]) == 0
        out = capsys.readouterr().out
        assert "in-loop achieved bandwidth" in out
        payload = json.loads(out.strip().splitlines()[-1])
        assert payload["kind"] == "section"
        assert payload["payload"]["classes"]["all-gather"][
            "achieved_gbps"] == 0.5

    def test_rejects_garbage(self, tmp_path, capsys):
        mod = _load_tool("comms_report")
        p = tmp_path / "nothing.json"
        p.write_text(json.dumps({"loss": 1.0}))
        assert mod.main([str(p), "--json", "-"]) == 2
        payload = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert payload["ok"] is False and "comms" in payload["error"]

    def test_metrics_report_section(self):
        mod = _load_tool("metrics_report")
        out = mod.comms_section({"comms": {
            "classes": {"all-gather": {"achieved_gbps": 0.5,
                                       "efficiency": 0.25}},
            "peak_bandwidth_gbps": 2.0}})
        assert "all-gather" in out and "achieved=0.500" in out
        assert "efficiency=25.0%" in out
        assert mod.comms_section({}) == ""


class TestCommsBenchCLI:
    def test_sweep_writes_summary_and_json_line(self, tmp_path, capsys):
        mod = _load_tool("comms_bench")
        rc = mod.main(["--sizes", "4096,16384", "--reps", "1",
                       "--warmup", "1", "--no-skew",
                       "--kinds", "all-gather,collective-permute",
                       "--out", str(tmp_path) + "/",
                       "--json", str(tmp_path / "bench.json")])
        assert rc == 0
        summary = comms.load_comms_summary(tmp_path)
        assert comms.is_comms_summary(summary)
        assert set(summary["axes"]) == {"dp", "pp", "tp"}
        payload = json.loads((tmp_path / "bench.json").read_text())
        assert payload["metric"] == "comms_bench_sweep"
        assert payload["value"] > 0
        assert payload["comms"]["axes"]["dp"]["bandwidth_gbps"] > 0
        out = capsys.readouterr().out
        assert "interconnect sweep" in out


if __name__ == "__main__":
    if "--regen-fixture" in sys.argv:
        FIXTURE.parent.mkdir(parents=True, exist_ok=True)
        comms.write_comms_summary(build_fixture(), FIXTURE)
        print(f"wrote {FIXTURE} ({FIXTURE.stat().st_size} bytes)")
    else:
        raise SystemExit(pytest.main([__file__, "-v"] + sys.argv[1:]))
