"""Perf contracts (analysis.perf_contract) + pipeline step timelines
(telemetry.step_timeline): the measured-runtime ratchet.

Covers the timeline reconstruction on a committed pp=2 fixture (tick
boundaries, per-stage busy/idle, measured bubble fraction, straggler
attribution), facts extraction from every accepted source, per-rule fault
injections proving each PC finding fires on a seeded regression, the
update-with-justification ratchet (refusal, byte-stability), cost-model
residual reports, the bench headline's mandatory contract-verdict field,
the CLI, and — the acceptance bar — live CPU-captured tiny-llama traces
for every manual-vjp pipeline schedule carrying measured bubble fraction +
per-stage busy/idle.  All tier-1 / CPU."""

import copy
import importlib.util
import json
from pathlib import Path

import pytest

from neuronx_distributed_training_tpu.analysis import perf_contract as pc
from neuronx_distributed_training_tpu.telemetry.step_timeline import (
    analyze_pipeline,
    pipeline_facts,
)

FIXTURE = Path(__file__).parent / "data" / "pipeline_trace_fixture.trace.json"


def _fixture_events():
    return json.loads(FIXTURE.read_text())["traceEvents"]


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# pipeline step-timeline reconstruction (committed pp=2 fixture)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fixture_pipeline():
    return analyze_pipeline(
        _fixture_events(), facts=pipeline_facts("1f1b", 2, 3, 1, 0.25))


class TestStepTimelineFixture:
    """The fixture encodes a pp=2 1f1b window [0, 800us): stage 0 computes
    ticks 0..6 and idles the drain tick 7; stage 1 idles the fill tick 0 and
    runs 80us compute + the 10us hop per tick after — so every number below
    is hand-computable."""

    def test_lanes_and_resolution(self, fixture_pipeline):
        p = fixture_pipeline
        assert p["num_lanes"] == 2
        assert p["lane_resolution"] == "device"
        assert sorted(p["stages"]) == ["/device:TPU:0", "/device:TPU:1"]
        assert p["window_seconds"] == pytest.approx(800e-6)

    def test_tick_boundaries_from_hop_markers(self, fixture_pipeline):
        # marker END times are the tick boundaries: 8 ticks per lane
        p = fixture_pipeline
        for s in p["stages"].values():
            assert s["ticks_detected"] == 8
        assert p["ticks_detected"] == 16
        assert not p["ticks_truncated"]
        rows = {(t["stage"], t["tick"]): t for t in p["ticks"]}
        assert len(rows) == 16
        assert rows[(0, 0)]["dur_us"] == pytest.approx(100.0)
        # stage 0 full through tick 6, drain-idle tick 7 (only the hop)
        assert rows[(0, 6)]["busy_fraction"] == pytest.approx(1.0)
        assert rows[(0, 7)]["busy_fraction"] == pytest.approx(0.1)
        # stage 1 fill-idle tick 0, then 90% busy (80us dot + 10us hop)
        assert rows[(1, 0)]["busy_fraction"] == pytest.approx(0.1)
        assert rows[(1, 5)]["busy_fraction"] == pytest.approx(0.9)

    def test_busy_idle_split(self, fixture_pipeline):
        s0 = fixture_pipeline["stages"]["/device:TPU:0"]
        s1 = fixture_pipeline["stages"]["/device:TPU:1"]
        assert s0["busy_seconds"] == pytest.approx(710e-6)
        assert s0["idle_seconds"] == pytest.approx(90e-6)
        assert s1["busy_seconds"] == pytest.approx(640e-6)
        assert s1["idle_seconds"] == pytest.approx(160e-6)
        # the nested all-gather adds collective time without double-counting
        # busy (it sits under a compute op)
        assert s0["collective_seconds"] == pytest.approx(110e-6)
        assert s0["compute_seconds"] == pytest.approx(630e-6)

    def test_measured_bubble_and_residual(self, fixture_pipeline):
        p = fixture_pipeline
        # idle (90 + 160) over lane-time (2 x 800)
        assert p["bubble_fraction_measured"] == pytest.approx(0.15625)
        assert p["bubble_fraction_predicted"] == pytest.approx(0.25)
        assert p["bubble_residual"] == pytest.approx(-0.09375)

    def test_straggler_attribution(self, fixture_pipeline):
        p = fixture_pipeline
        assert p["straggler_stage"] == "/device:TPU:0"
        assert p["straggler_busy_fraction"] == pytest.approx(710 / 800,
                                                             abs=1e-4)

    def test_schedule_facts_echoed(self, fixture_pipeline):
        p = fixture_pipeline
        assert (p["schedule"], p["pp"], p["num_microbatches"], p["vp"]) == \
            ("1f1b", 2, 3, 1)


class TestStepTimelineEdges:
    def test_no_pp_means_no_section(self):
        assert analyze_pipeline(
            _fixture_events(), facts=pipeline_facts("none", 1, 4)) is None
        assert analyze_pipeline(_fixture_events(), facts=None) is None

    def test_no_ops_means_no_section(self):
        assert analyze_pipeline([], facts=pipeline_facts("1f1b", 2, 4)) is None

    def test_window_fallback_without_step_annotations(self):
        # drop the StepTraceAnnotation: the span falls back to op extent
        events = [e for e in _fixture_events()
                  if "step_num" not in (e.get("args") or {})]
        p = analyze_pipeline(events, facts=pipeline_facts("1f1b", 2, 3))
        assert p is not None
        assert p["window_seconds"] == pytest.approx(800e-6)
        assert p["bubble_fraction_predicted"] is None
        assert "bubble_residual" not in p

    def test_single_lane_is_aggregate(self):
        events = [e for e in _fixture_events() if e.get("pid") != 2]
        p = analyze_pipeline(events, facts=pipeline_facts("1f1b", 2, 3))
        assert p["lane_resolution"] == "aggregate"
        assert p["num_lanes"] == 1

    def test_stage_indices_follow_numeric_device_order(self):
        # 12 lanes: lexicographic order would rank TPU:10/11 before TPU:2,
        # scrambling stage attribution on every pp >= 10 capture
        events = []
        for i in range(12):
            events.append({"ph": "M", "pid": i + 1, "name": "process_name",
                           "args": {"name": f"/device:TPU:{i}"}})
            events.append({"ph": "X", "pid": i + 1, "tid": 1,
                           "ts": i * 10, "dur": 5, "name": "fusion.1"})
            events.append({"ph": "X", "pid": i + 1, "tid": 1,
                           "ts": i * 10 + 5, "dur": 2,
                           "name": "collective-permute.1"})
        p = analyze_pipeline(events, facts=pipeline_facts("1f1b", 12, 4))
        assert p["num_lanes"] == 12
        for i in range(12):
            assert p["stages"][f"/device:TPU:{i}"]["stage"] == i

    def test_tick_rows_capped_but_counted(self):
        p = analyze_pipeline(_fixture_events(),
                             facts=pipeline_facts("1f1b", 2, 3),
                             max_tick_rows=5)
        assert len(p["ticks"]) == 5
        assert p["ticks_detected"] == 16
        assert p["ticks_truncated"]

    def test_analyze_events_embeds_section(self):
        from neuronx_distributed_training_tpu.telemetry.trace_analysis import (
            analyze_events,
        )

        s = analyze_events(_fixture_events(),
                           pipeline=pipeline_facts("1f1b", 2, 3, 1, 0.25))
        assert s["pipeline"]["bubble_fraction_measured"] == pytest.approx(
            0.15625)
        # without facts the summary shape is unchanged
        assert "pipeline" not in analyze_events(_fixture_events())


# ---------------------------------------------------------------------------
# facts extraction
# ---------------------------------------------------------------------------


def _bench_line(**over):
    line = {
        "metric": "llama3_8B_pretrain_mfu", "value": 66.59,
        "unit": "percent_mfu", "vs_baseline": 1.48,
        "regime": "mixed_precision", "device": "TPU v5 lite",
        "seq_len": 8192, "num_layers": 9, "pipeline_schedule": "none",
        "ms_per_step": 905.0, "tokens_per_sec_per_chip": 28950.0,
        "mfu": 0.6659, "achieved_overlap": 0.62,
        "exposed_collective_seconds": 0.031,
        "overlap_by_class": {"all-gather": 0.55, "reduce-scatter": 0.71},
        "bubble_fraction_predicted": 0.0,
    }
    line.update(over)
    return line


def _facts(**over):
    """Canonical facts with a full measured surface (the differ's input)."""
    f = pc.perf_facts_from_bench(_bench_line())
    f["overlap_by_class"] = {
        "all-gather": {"achieved_overlap": 0.55, "exposed_seconds": 0.8,
                       "wire_seconds": 1.8},
        "reduce-scatter": {"achieved_overlap": 0.71, "exposed_seconds": 0.2,
                           "wire_seconds": 0.7},
    }
    f["bubble_fraction_measured"] = 0.10
    f["bubble_fraction_predicted"] = 0.12
    f["residuals"] = {"total": {"ratio": 1.10}}
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(f.get(k), dict):
            f[k] = copy.deepcopy(f[k])
            f[k].update(v)
        else:
            f[k] = v
    return f


class TestFactsExtraction:
    def test_from_bench_line(self):
        f = pc.perf_facts_from_bench(_bench_line())
        assert f["version"] == pc.FACTS_VERSION
        assert f["step_time_ms"] == pytest.approx(905.0)
        assert f["mfu"] == pytest.approx(0.6659)
        assert f["workload"]["device"] == "TPU v5 lite"
        assert f["overlap_by_class"]["all-gather"]["achieved_overlap"] == \
            pytest.approx(0.55)

    def test_zero_bubble_fraction_survives_extraction(self):
        # a fully-busy aggregate lane rounds to exactly 0.0 — falsy, but a
        # MEASUREMENT; it must not fall through to None (which would
        # silently disable the PC301 bubble ratchet for the topology)
        f = pc.perf_facts_from_bench(_bench_line(bubble_fraction_measured=0.0))
        assert f["bubble_fraction_measured"] == 0.0

    def test_mfu_falls_back_to_percent_value(self):
        line = _bench_line()
        del line["mfu"]
        f = pc.perf_facts_from_bench(line)
        assert f["mfu"] == pytest.approx(0.6659)

    def test_from_trace_summary(self):
        summary = {
            "achieved_overlap": 0.4, "exposed_collective_seconds": 0.02,
            "top_ops": [],
            "overlap_by_class": {"all-reduce": {
                "achieved_overlap": 0.4, "exposed_seconds": 0.02,
                "wire_seconds": 0.033}},
            "pipeline": {"schedule": "1f1b",
                         "bubble_fraction_measured": 0.21,
                         "bubble_fraction_predicted": 0.25},
        }
        f = pc.perf_facts_from_trace_summary(summary)
        assert f["bubble_fraction_measured"] == pytest.approx(0.21)
        assert f["step_time_ms"] is None
        assert f["workload"]["schedule"] == "1f1b"

    def test_from_run_dir(self, tmp_path):
        (tmp_path / "run_summary.json").write_text(json.dumps({
            "model_family": "LlamaConfig", "n_chips": 8, "seq_len": 32,
            "global_batch_size": 8, "pipeline_schedule": "1f1b",
            "bubble_fraction_predicted": 0.3333,
        }))
        (tmp_path / "trace_summary.json").write_text(json.dumps({
            "achieved_overlap": 0.5, "exposed_collective_seconds": 0.01,
            "overlap_by_class": {},
            "pipeline": {"bubble_fraction_measured": 0.08,
                         "schedule": "1f1b"},
        }))
        (tmp_path / "metrics.jsonl").write_text(
            json.dumps({"step": 3, "mfu": 0.02,
                        "tokens_per_sec_per_chip": 1000.0}) + "\n"
            + "{torn line")
        f = pc.perf_facts_from_run(tmp_path)
        assert f["mfu"] == pytest.approx(0.02)
        assert f["bubble_fraction_measured"] == pytest.approx(0.08)
        assert f["bubble_fraction_predicted"] == pytest.approx(0.3333)
        # step time derives from the SAME throughput window MFU uses:
        # gbs * seq / (tokens_per_sec_per_chip * chips)
        assert f["step_time_ms"] == pytest.approx(8 * 32 / 8000 * 1e3)

    def test_load_facts_dispatch(self, tmp_path):
        bench = tmp_path / "bench.json"
        bench.write_text(json.dumps(_bench_line()))
        assert pc.load_facts(bench)["step_time_ms"] == pytest.approx(905.0)
        # stdout capture: the JSON line is the LAST parseable line
        noisy = tmp_path / "capture.txt"
        noisy.write_text("bench: warmup done\n"
                         + json.dumps(_bench_line(ms_per_step=1.0)) + "\n")
        assert pc.load_facts(noisy)["step_time_ms"] == pytest.approx(1.0)
        # jsonl evidence log: last line wins
        log = tmp_path / "measured.jsonl"
        log.write_text(json.dumps(_bench_line(ms_per_step=2.0)) + "\n"
                       + json.dumps(_bench_line(ms_per_step=3.0)) + "\n")
        assert pc.load_facts(log)["step_time_ms"] == pytest.approx(3.0)
        # canonical facts pass through
        assert pc.load_facts(_facts())["version"] == pc.FACTS_VERSION
        with pytest.raises(pc.PerfContractError):
            pc.load_facts(tmp_path / "missing.json")
        with pytest.raises(pc.PerfContractError):
            pc.load_facts({"unrecognized": True})

    def test_default_key(self):
        assert pc.default_key(_facts()) == "tpu_v5_lite_bench"
        f = pc.perf_facts_from_bench(_bench_line(device="cpu"))
        assert pc.default_key(f) == "cpu_bench"


# ---------------------------------------------------------------------------
# the differ: every PC rule fires on a seeded regression
# ---------------------------------------------------------------------------


def _rules(report):
    return {f.rule for f in report.findings}


class TestDiffRules:
    def test_in_band_drift_is_clean(self):
        old = _facts()
        new = _facts(step_time_ms=old["step_time_ms"] * 1.05)
        rep = pc.diff_facts(old, new)
        assert not rep.findings, rep.format()

    def test_pc101_step_time_growth(self):
        rep = pc.diff_facts(_facts(), _facts(step_time_ms=905.0 * 1.5))
        assert _rules(rep) == {"PC101"}
        assert rep.failed("error")
        f = rep.findings[0]
        assert "905" in f.message and "25%" in f.message

    def test_pc102_mfu_fall(self):
        rep = pc.diff_facts(_facts(), _facts(mfu=0.55))
        assert _rules(rep) == {"PC102"}

    def test_pc102_throughput_without_mfu(self):
        old, new = _facts(mfu=None), _facts(mfu=None,
                                            tokens_per_sec=28950.0 * 0.5)
        rep = pc.diff_facts(old, new)
        assert _rules(rep) == {"PC102"}
        assert "tokens/sec" in rep.findings[0].message

    def test_pc110_improvement_is_info(self):
        rep = pc.diff_facts(_facts(), _facts(step_time_ms=905.0 * 0.5))
        assert _rules(rep) == {"PC110"}
        assert not rep.failed("error")

    def test_pc201_per_class_overlap_fall_names_class(self):
        new = _facts(overlap_by_class={
            "all-gather": {"achieved_overlap": 0.20, "exposed_seconds": 0.8,
                           "wire_seconds": 1.8}})
        rep = pc.diff_facts(_facts(), new)
        assert _rules(rep) == {"PC201"}
        f = rep.findings[0]
        assert "all-gather" in f.message and "ZeRO-1" in f.message
        assert f.location == "all-gather"

    def test_pc202_exposed_growth_names_class_and_axes(self):
        new = _facts(overlap_by_class={
            "all-gather": {"achieved_overlap": 0.55, "exposed_seconds": 2.1,
                           "wire_seconds": 3.1}})
        rep = pc.diff_facts(_facts(), new)
        assert _rules(rep) == {"PC202"}
        msg = rep.findings[0].message
        assert "exposed all-gather seconds grew" in msg
        assert "0.8s -> 2.1s" in msg and "[dp/tp]" in msg

    def test_pc202_total_exposed_growth(self):
        old = _facts(overlap_by_class={})
        new = _facts(overlap_by_class={},
                     exposed_collective_seconds=0.031 * 3)
        rep = pc.diff_facts(old, new)
        assert _rules(rep) == {"PC202"}
        assert rep.findings[0].location == "overall"

    def test_pc301_measured_bubble_growth(self):
        rep = pc.diff_facts(_facts(), _facts(bubble_fraction_measured=0.30,
                                             bubble_fraction_predicted=0.32))
        assert _rules(rep) == {"PC301"}
        assert "bubble" in rep.findings[0].message

    def test_pc302_measured_beyond_predicted_band(self):
        # baseline-independent: fires even when the baseline agrees
        old = _facts(bubble_fraction_measured=0.30,
                     bubble_fraction_predicted=0.12)
        new = _facts(bubble_fraction_measured=0.30,
                     bubble_fraction_predicted=0.12)
        rep = pc.diff_facts(old, new)
        assert _rules(rep) == {"PC302"}
        assert "calibration band" in rep.findings[0].message

    def test_pc401_residual_drift(self):
        rep = pc.diff_facts(
            _facts(), _facts(residuals={"total": {"ratio": 1.60}}))
        assert _rules(rep) == {"PC401"}
        assert "decalibrated" in rep.findings[0].message

    def test_pc001_workload_identity_mismatch_short_circuits(self):
        new = _facts(step_time_ms=9999.0)
        new["workload"] = dict(new["workload"], seq_len=4096)
        rep = pc.diff_facts(_facts(), new)
        assert _rules(rep) == {"PC001"}  # nothing else compared
        assert "seq_len" in rep.findings[0].message

    def test_pc001_version_mismatch(self):
        old = _facts()
        old["version"] = 0
        rep = pc.diff_facts(old, _facts())
        assert _rules(rep) == {"PC001"}

    def test_custom_noise_bands_respected(self):
        rep = pc.diff_facts(_facts(), _facts(step_time_ms=905.0 * 1.5),
                            noise={"step_time_frac": 1.0})
        assert not rep.findings


# ---------------------------------------------------------------------------
# the ratchet: baselines, refusal, byte-stability
# ---------------------------------------------------------------------------


class TestRatchet:
    def test_no_baseline_is_pc000(self, tmp_path):
        rep = pc.check_perf("v9z_bench", _facts(), baselines_dir=tmp_path)
        assert _rules(rep) == {"PC000"}
        assert rep.stats["no_baseline"] is True

    def test_update_then_check_round_trip(self, tmp_path):
        path, rep = pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        assert path.exists() and not rep.findings
        rep = pc.check_perf("k", _facts(), baselines_dir=tmp_path)
        assert not rep.findings
        snap = json.loads(path.read_text())
        assert snap["justifications"] == ["initial perf baseline"]
        assert snap["noise"]["step_time_frac"] == pytest.approx(
            pc.DEFAULT_NOISE["step_time_frac"])

    def test_rewrite_is_byte_stable(self, tmp_path):
        path, _ = pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        first = path.read_bytes()
        path2, _ = pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        assert path2 == path and path.read_bytes() == first

    def test_regression_refuses_without_justify(self, tmp_path):
        path, _ = pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        before = path.read_bytes()
        with pytest.raises(pc.PerfContractError, match="PC101"):
            pc.update_baseline("k", _facts(step_time_ms=905.0 * 2),
                               baselines_dir=tmp_path)
        # a refused update must leave the committed file untouched
        assert path.read_bytes() == before

    def test_justified_regression_recorded_in_file(self, tmp_path):
        pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        path, rep = pc.update_baseline(
            "k", _facts(step_time_ms=905.0 * 2),
            justify="remat default flipped: +2x step for -40% HBM",
            baselines_dir=tmp_path)
        snap = json.loads(path.read_text())
        assert snap["justifications"][-1].startswith("remat default flipped")
        assert snap["facts"]["step_time_ms"] == pytest.approx(1810.0)

    def test_improvement_commits_silently(self, tmp_path):
        pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        path, rep = pc.update_baseline(
            "k", _facts(step_time_ms=905.0 * 0.5), baselines_dir=tmp_path)
        snap = json.loads(path.read_text())
        assert snap["justifications"] == ["initial perf baseline"]
        assert snap["facts"]["step_time_ms"] == pytest.approx(452.5)
        assert {f.rule for f in rep.findings} == {"PC110"}

    def test_baseline_noise_bands_drive_the_check(self, tmp_path):
        pc.update_baseline("k", _facts(), baselines_dir=tmp_path,
                           noise={"step_time_frac": 3.0})
        rep = pc.check_perf("k", _facts(step_time_ms=905.0 * 4.5),
                            baselines_dir=tmp_path)
        assert _rules(rep) == {"PC101"}
        rep = pc.check_perf("k", _facts(step_time_ms=905.0 * 3.5),
                            baselines_dir=tmp_path)
        assert not rep.findings

    def test_bench_verdict_shapes(self, tmp_path):
        v = pc.bench_verdict("k", _facts(), baselines_dir=tmp_path)
        assert v == {"key": "k", "verdict": "no_baseline",
                     "no_baseline": True}
        pc.update_baseline("k", _facts(), baselines_dir=tmp_path)
        assert pc.bench_verdict("k", _facts(),
                                baselines_dir=tmp_path)["verdict"] == "clean"
        v = pc.bench_verdict("k", _facts(step_time_ms=905.0 * 2),
                             baselines_dir=tmp_path)
        assert v["verdict"] == "error"
        assert v["findings"][0]["rule"] == "PC101"

    def test_committed_cpu_baseline_exists_and_loads(self):
        # the verify-gate baseline shipped with the repo
        snap = pc.load_baseline("cpu_bench")
        assert snap is not None
        assert snap["facts"]["workload"]["device"] == "cpu"
        assert snap["noise"]["step_time_frac"] >= 1.0  # CPU wall clocks vary


# ---------------------------------------------------------------------------
# cost-model residuals
# ---------------------------------------------------------------------------


class TestResiduals:
    EST = {"step_seconds": 0.10, "compute_seconds": 0.07,
           "comms_seconds": 0.02, "bubble_seconds": 0.01}

    def test_total_only(self):
        r = pc.residual_report(self.EST, {"step_seconds": 0.15})
        assert r["total"]["ratio"] == pytest.approx(1.5)
        assert r["comms"]["measured_exposed_seconds"] is None
        assert r["comms"]["ratio"] is None
        assert r["bubble"]["measured_fraction"] is None
        assert r["compute"]["measured_seconds"] is None

    def test_full_surface(self):
        r = pc.residual_report(self.EST, {
            "step_seconds": 0.12, "exposed_collective_seconds": 0.03,
            "bubble_fraction_measured": 0.25})
        assert r["total"]["ratio"] == pytest.approx(1.2)
        assert r["comms"]["ratio"] == pytest.approx(1.5)
        assert r["bubble"]["predicted_fraction"] == pytest.approx(0.1)
        assert r["bubble"]["residual"] == pytest.approx(0.15)
        # measured compute = step - exposed - bubble*step
        assert r["compute"]["measured_seconds"] == pytest.approx(
            0.12 - 0.03 - 0.25 * 0.12)

    def test_never_negative_compute(self):
        r = pc.residual_report(self.EST, {
            "step_seconds": 0.01, "exposed_collective_seconds": 0.05,
            "bubble_fraction_measured": 0.5})
        assert r["compute"]["measured_seconds"] == 0.0


# ---------------------------------------------------------------------------
# bench.py: the mandatory contract-verdict field; no device -> non-zero exit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_mod():
    path = Path(__file__).resolve().parents[1] / "bench.py"
    spec = importlib.util.spec_from_file_location("bench_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchContract:
    def test_emit_refuses_headline_without_verdict(self, bench_mod):
        with pytest.raises(RuntimeError, match="perf_contract"):
            bench_mod.emit({"metric": "llama3_8B_pretrain_mfu", "value": 1.0})

    def test_emit_accepts_headline_with_verdict(self, bench_mod, capsys):
        bench_mod.emit({"metric": "llama3_8B_pretrain_mfu", "value": 1.0,
                        "perf_contract": {"verdict": "no_baseline"}})
        line = json.loads(capsys.readouterr().out.strip())
        assert line["perf_contract"]["verdict"] == "no_baseline"

    def test_non_headline_lines_unaffected(self, bench_mod, capsys):
        bench_mod.emit({"note": "not a metric line"})
        assert json.loads(capsys.readouterr().out.strip())["note"]

    def test_no_device_exits_nonzero_and_emits_nothing(self, bench_mod,
                                                       capsys):
        """With no TPU and no ``--platform cpu`` the stripped bench exits
        non-zero and prints no JSON line: a measurement that did not happen
        leaves no record (this suite's backend is the CPU)."""
        with pytest.raises(SystemExit) as exc:
            bench_mod.acquire_device(None)
        assert exc.value.code not in (0, None)
        assert "no TPU" in str(exc.value.code)
        assert capsys.readouterr().out == ""
        assert bench_mod.acquire_device("cpu").platform == "cpu"
        # the fail-soft line and the carried-over "last" measurement are gone
        assert not hasattr(bench_mod, "fail_json")
        assert not [n for n in dir(bench_mod) if "measured" in n.lower()]


# ---------------------------------------------------------------------------
# tools/perf_contract.py CLI
# ---------------------------------------------------------------------------


class TestPerfContractCLI:
    def _run(self, tool, argv):
        with pytest.raises(SystemExit) as exc:
            tool.main(argv)
        return exc.value.code

    def test_check_no_baseline_fails_then_allow_missing(self, tmp_path,
                                                        capsys):
        tool = _load_tool("perf_contract")
        src = tmp_path / "bench.json"
        src.write_text(json.dumps(_bench_line()))
        rc = self._run(tool, ["--check", str(src),
                              "--baselines-dir", str(tmp_path / "b")])
        assert rc == 1
        assert "no_baseline" in capsys.readouterr().out
        rc = self._run(tool, ["--check", str(src), "--allow-missing",
                              "--baselines-dir", str(tmp_path / "b")])
        assert rc == 0

    def test_update_check_regress_cycle_with_json(self, tmp_path, capsys):
        tool = _load_tool("perf_contract")
        bdir = str(tmp_path / "b")
        good = tmp_path / "good.json"
        good.write_text(json.dumps(_bench_line()))
        assert self._run(tool, ["--update-baselines", str(good),
                                "--baselines-dir", bdir]) == 0
        assert self._run(tool, ["--check", str(good),
                                "--baselines-dir", bdir]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(_bench_line(ms_per_step=905.0 * 2)))
        capsys.readouterr()
        rc = self._run(tool, ["--check", str(bad), "--baselines-dir", bdir,
                              "--json", "-"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "PC101" in out
        payload = json.loads(out.strip().splitlines()[-1])  # last-line JSON
        assert payload["reports"][0]["verdict"] == "error"
        # the refused update leaves no trace either
        assert self._run(tool, ["--update-baselines", str(bad),
                                "--baselines-dir", bdir]) == 1
        assert self._run(tool, ["--update-baselines", str(bad),
                                "--baselines-dir", bdir,
                                "--justify", "deliberate"]) == 0

    def test_unknown_noise_band_rejected(self, tmp_path, capsys):
        tool = _load_tool("perf_contract")
        src = tmp_path / "bench.json"
        src.write_text(json.dumps(_bench_line()))
        rc = self._run(tool, ["--check", str(src), "--noise", "bogus=1"])
        assert rc == 2  # argparse error


# ---------------------------------------------------------------------------
# report surfaces
# ---------------------------------------------------------------------------


class TestReportSurfaces:
    def test_trace_report_renders_pipeline_section(self, tmp_path, capsys):
        from neuronx_distributed_training_tpu.telemetry.trace_analysis import (
            analyze_events,
        )

        tr = _load_tool("trace_report")
        summary = analyze_events(_fixture_events(),
                                 pipeline=pipeline_facts("1f1b", 2, 3, 1,
                                                         0.25))
        p = tmp_path / "trace_summary.json"
        p.write_text(json.dumps(summary))
        assert tr.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "pipeline timeline" in out
        assert "bubble_fraction_measured" in out
        assert "straggler_stage" in out
        assert "/device:TPU:0" in out and "/device:TPU:1" in out
        assert "tick gantt" in out

    def test_trace_report_gantt_aligns_unequal_tick_counts(self, capsys):
        """Compacted timelines: stages detect different tick counts, so the
        Gantt columns are TIME buckets — a stage with fewer ticks must not
        be stretched to the full axis (the old per-tick-index rendering
        assumed a shared tick axis)."""
        tr = _load_tool("trace_report")
        summary = {"pipeline": {
            "schedule": "1f1b", "pp": 2, "num_microbatches": 4, "vp": 1,
            "lane_resolution": "device", "num_lanes": 2,
            "bubble_fraction_measured": 0.2,
            "stages": {"/device:TPU:0": {"stage": 0, "ticks_detected": 4,
                                         "busy_seconds": 1.0},
                       "/device:TPU:1": {"stage": 1, "ticks_detected": 2,
                                         "busy_seconds": 1.0}},
            "straggler_stage": "/device:TPU:0",
            "ticks": (
                # stage 0: four 100us ticks covering [0, 400us)
                [{"stage": 0, "tick": t, "start_us": t * 100.0,
                  "dur_us": 100.0, "busy_fraction": 1.0} for t in range(4)]
                # stage 1: TWO ticks, busy only in the middle [100, 300us)
                + [{"stage": 1, "tick": 0, "start_us": 100.0,
                    "dur_us": 100.0, "busy_fraction": 1.0},
                   {"stage": 1, "tick": 1, "start_us": 200.0,
                    "dur_us": 100.0, "busy_fraction": 1.0}]),
        }}
        out = tr.render(summary)
        bars = {}
        for line in out.splitlines():
            if "|" in line and "stage" in line:
                stage = int(line.split("|")[0].split()[-1])
                bars[stage] = line.split("|")[1]
        # shared time axis: equal bar widths, 4 buckets
        assert len(bars[0]) == len(bars[1]) == 4
        assert bars[0] == "####"
        # stage 1's ticks cover only [100, 300): idle columns at both ends
        assert bars[1] == " ## "

    def test_metrics_report_renders_verdict(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        line = dict(_bench_line(),
                    perf_contract={"verdict": "error", "key": "cpu_bench",
                                   "findings": [{"rule": "PC101",
                                                 "message": "step time grew"}]},
                    bubble_fraction_measured=0.11)
        p = tmp_path / "BENCH_test.json"
        p.write_text(json.dumps(line))
        assert mr.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "perf contract" in out and "PC101" in out
        assert "bubble_fraction_measured" in out

    def test_planner_calibration_audit_trail(self, tmp_path):
        from neuronx_distributed_training_tpu.autotune import plan_config
        from neuronx_distributed_training_tpu.telemetry.trace_analysis import (
            analyze_events,
        )

        summary = analyze_events(_fixture_events(),
                                 pipeline=pipeline_facts("1f1b", 2, 3, 1,
                                                         0.25))
        p = tmp_path / "trace_summary.json"
        p.write_text(json.dumps(summary))
        cfg = {
            "name": "t", "model_source": "hf",
            "trainer": {"max_steps": 1},
            "distributed_strategy": {"tensor_model_parallel_size": 2},
            "data": {"seq_length": 64, "global_batch_size": 8,
                     "micro_batch_size": 1, "synthetic": True},
            "model": {"architecture": "llama", "vocab_size": 256,
                      "hidden_size": 64, "intermediate_size": 128,
                      "num_layers": 4, "num_attention_heads": 4,
                      "num_key_value_heads": 2,
                      "max_position_embeddings": 64},
            "precision": {"type": "mixed_precision"},
        }
        rep = plan_config(cfg, chips=8, topology="v5e", audit=False,
                          top_k=3, calibration=str(p))
        assert rep.error is None
        cf = rep.calibration_facts
        assert cf is not None
        assert cf["bubble_fraction_measured"] == pytest.approx(0.15625)
        assert "calibration audit" in rep.format()
        assert "calibration_facts" in rep.to_dict()
        # pp plans exist on 8 chips: when the winner is pipelined the audit
        # records its predicted fraction + the residual
        if cf.get("winner_bubble_residual") is not None:
            assert cf["winner_bubble_fraction_predicted"] is not None


# ---------------------------------------------------------------------------
# acceptance: live CPU-captured tiny-llama traces, every manual-vjp schedule
# ---------------------------------------------------------------------------


def _pp_cfg(tmp_path, schedule, vp=1, num_layers=2):
    return {
        "name": f"pt_{schedule.replace('-', '_')}", "model_source": "hf",
        "seed": 7,
        "trainer": {"max_steps": 4, "log_every_n_steps": 1},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False,
                        "telemetry": {"trace": {"enabled": True,
                                                "start_step": 1,
                                                "num_steps": 2}}},
        "distributed_strategy": {
            "pipeline_model_parallel_size": 2,
            **({"virtual_pipeline_model_parallel_size": vp} if vp > 1
               else {}),
            "pipeline": {"schedule": schedule},
        },
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        "model": {"vocab_size": 128, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": num_layers,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    }


@pytest.mark.parametrize("schedule,vp,layers", [
    ("1f1b", 1, 2),
    ("1f1b-zb", 1, 2),
    ("1f1b-interleaved", 2, 4),
])
def test_live_manual_vjp_schedule_trace_carries_measured_bubble(
        tmp_path, devices8, schedule, vp, layers):
    """The acceptance bar: a CPU-captured tiny-llama trace for EVERY
    manual-vjp schedule must land measured bubble fraction + per-stage
    busy/idle in trace_summary.json, and run_summary.json must carry
    bubble_fraction_measured beside bubble_fraction_predicted."""
    import numpy as np

    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(_pp_cfg(tmp_path, schedule, vp=vp, num_layers=layers))
    t = Trainer.from_config(cfg, enable_checkpointing=False)
    assert t.pipeline_schedule == schedule
    metrics = t.fit()
    assert np.isfinite(metrics["loss"])
    run = (tmp_path / "exp" / cfg["name"] / "version_0")
    summary = json.loads((run / "trace_summary.json").read_text())
    pipe = summary.get("pipeline")
    assert pipe is not None, "traced pp run must carry the pipeline section"
    assert pipe["schedule"] == schedule and pipe["pp"] == 2
    mb = pipe["bubble_fraction_measured"]
    assert mb is not None and 0.0 <= mb <= 1.0
    assert pipe["stages"], "per-stage busy/idle table missing"
    for s in pipe["stages"].values():
        assert s["busy_seconds"] > 0
        assert s["idle_seconds"] >= 0
        assert s["ticks_detected"] > 0
    assert pipe["straggler_stage"] in pipe["stages"]
    # predicted fraction rides along so the residual is self-contained
    assert pipe["bubble_fraction_predicted"] == pytest.approx(
        json.loads((run / "run_summary.json").read_text())
        ["bubble_fraction_predicted"], abs=1e-6)
    run_summary = json.loads((run / "run_summary.json").read_text())
    assert run_summary["bubble_fraction_measured"] == pytest.approx(mb)
    assert run_summary["trace"]["pipeline"]["schedule"] == schedule
    # and the perf-contract facts extractor reads the run dir whole
    facts = pc.perf_facts_from_run(run)
    assert facts["bubble_fraction_measured"] == pytest.approx(mb)


# ---------------------------------------------------------------------------
# compacted executions: committed pp=2 fixture where tick count != lockstep T
# ---------------------------------------------------------------------------


COMPACTED_FIXTURE = Path(__file__).parent / "data" \
    / "pipeline_trace_compacted_fixture.trace.json"


class TestCompactedTimelineFixture:
    """The work-compacted executor's timeline: the committed fixture encodes
    a pp=2 1f1b nm=4 COMPACTED window [0, 600us) — span 6 ticks where the
    lockstep trip count was 7.  Stage 0 runs F full ticks 0..4 and a 40us
    drain tail; stage 1 fill-idles tick 0 (only the gated hop runs) and
    drain-idles tick 5.  Every number is hand-computable, and the fill/drain
    idle is now VISIBLE idle (the lockstep executor burned compute there —
    the 'no phantom masked-tick compute' property)."""

    @pytest.fixture(scope="class")
    def compacted(self):
        from neuronx_distributed_training_tpu.parallel.pipeline import (
            predicted_bubble_fraction,
            work_table,
        )

        events = json.loads(COMPACTED_FIXTURE.read_text())["traceEvents"]
        return analyze_pipeline(events, facts=pipeline_facts(
            "1f1b", 2, 4, 1, predicted_bubble_fraction("1f1b", 2, 4, 1),
            ticks_per_step=work_table("1f1b", 2, 4, 1).tick_counts()))

    def test_tick_count_is_compacted_not_lockstep(self, compacted):
        p = compacted
        # 6 compacted ticks per lane resolved from the pp-hop markers —
        # NOT the lockstep T = nm + 2pp - 1 = 7
        lockstep = p["ticks_per_step"]["lockstep_span"]
        assert lockstep == 7
        for s in p["stages"].values():
            assert s["ticks_detected"] == 6
        assert p["ticks_detected"] == 12
        assert p["ticks_per_step"]["span"] == 6
        assert p["ticks_per_step"]["f_ticks"] == 5
        assert p["ticks_per_step"]["b_ticks"] == 5

    def test_busy_idle_split(self, compacted):
        s0 = compacted["stages"]["/device:TPU:0"]
        s1 = compacted["stages"]["/device:TPU:1"]
        # stage 0: 5 full ticks + (40us tail + 10us hop) in the drain tick
        assert s0["busy_seconds"] == pytest.approx(550e-6)
        assert s0["idle_seconds"] == pytest.approx(50e-6)
        # stage 1: fill tick 0 and drain tick 5 are 10us hop + 90us IDLE —
        # real idle, not burned masked compute
        assert s1["busy_seconds"] == pytest.approx(420e-6)
        assert s1["idle_seconds"] == pytest.approx(180e-6)

    def test_measured_bubble_lands_in_band(self, compacted):
        p = compacted
        # idle (50 + 180) over lane-time (2 x 600)
        assert p["bubble_fraction_measured"] == pytest.approx(230 / 1200,
                                                              abs=1e-6)
        # the compacted prediction is the table's own accounting: 0.2 for
        # 1f1b pp=2 nm=4 — the measurement lands within the PC302 band
        assert p["bubble_fraction_predicted"] == pytest.approx(0.2)
        assert abs(p["bubble_residual"]) < pc.DEFAULT_NOISE["bubble_abs"]

    def test_no_pc302_on_compacted_run(self, compacted):
        from neuronx_distributed_training_tpu.analysis.report import (
            AuditReport,
        )

        facts = pc.perf_facts_from_trace_summary({"pipeline": compacted})
        rep = AuditReport(config="t")
        pc.calibration_findings(facts, pc.DEFAULT_NOISE, rep)
        assert not [f for f in rep.findings if f.rule == "PC302"]

    def test_ticks_per_step_passthrough(self, compacted):
        # the facts' expected tick counts are echoed so a reader can tell
        # compaction from a broken marker chain
        assert compacted["ticks_per_step"]["w_ticks"] == 0
        assert compacted["ticks_per_step"]["head_ticks"] == 4


# ---------------------------------------------------------------------------
# schedule-sweep contract rules (PC302 per row, PC303 ordering, row ratchet)
# ---------------------------------------------------------------------------


def _sweep_line(rows=None, **over):
    line = {
        "metric": "pipeline_schedule_sweep", "value": 0.93,
        "unit": "interleaved_over_1f1b_step_time_ratio",
        "vs_baseline": 0.93, "device": "cpu", "seq_len": 64,
        "num_layers": 8, "pipeline_schedule": "sweep",
        "schedule_sweep": {
            "pp": 2, "nm": 16, "vp": 2,
            "interleaved_over_1f1b": 0.93,
            "rows": rows if rows is not None else [
                {"schedule": "wavefront", "ms_per_step": 1680.0,
                 "bubble_fraction_measured": 0.05,
                 "bubble_fraction_predicted": 0.0303},
                {"schedule": "1f1b", "ms_per_step": 1850.0,
                 "bubble_fraction_measured": 0.06,
                 "bubble_fraction_predicted": 0.0588},
                {"schedule": "1f1b-interleaved", "ms_per_step": 1717.0,
                 "bubble_fraction_measured": 0.05,
                 "bubble_fraction_predicted": 0.0303},
                {"schedule": "1f1b-zb", "ms_per_step": 2754.0,
                 "bubble_fraction_measured": 0.07,
                 "bubble_fraction_predicted": 0.0361},
            ],
        },
    }
    line.update(over)
    return line


class TestScheduleSweepRules:
    def test_facts_extraction_normalizes_rows(self):
        f = pc.perf_facts_from_bench(_sweep_line())
        rows = {r["schedule"]: r for r in f["schedule_sweep"]}
        assert set(rows) == {"wavefront", "1f1b", "1f1b-interleaved",
                             "1f1b-zb"}
        assert rows["1f1b"]["step_time_ms"] == pytest.approx(1850.0)
        assert rows["1f1b-interleaved"]["bubble_fraction_predicted"] == \
            pytest.approx(0.0303)

    def test_default_key_separates_sweep_from_headline(self):
        f = pc.perf_facts_from_bench(_sweep_line())
        assert pc.default_key(f) == "cpu_schedule_sweep"
        assert pc.default_key(pc.perf_facts_from_bench(_bench_line())) \
            == "tpu_v5_lite_bench"

    def _check(self, facts, noise=None):
        from neuronx_distributed_training_tpu.analysis.report import (
            AuditReport,
        )

        rep = AuditReport(config="t")
        pc.calibration_findings(facts, dict(pc.DEFAULT_NOISE, **(noise or {})),
                                rep)
        return rep

    def test_sweep_in_band_is_clean(self):
        rep = self._check(pc.perf_facts_from_bench(_sweep_line()))
        assert not rep.findings, rep.format()

    def test_pc302_fires_per_row_naming_schedule(self):
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[2]["bubble_fraction_measured"] = 0.30  # interleaved idles
        rep = self._check(pc.perf_facts_from_bench(_sweep_line(rows=rows)))
        hits = [f for f in rep.findings if f.rule == "PC302"]
        assert len(hits) == 1
        assert hits[0].location == "1f1b-interleaved"
        assert "1f1b-interleaved" in hits[0].message

    def test_pc302_band_is_in_file_noise(self):
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[2]["bubble_fraction_measured"] = 0.30
        rep = self._check(pc.perf_facts_from_bench(_sweep_line(rows=rows)),
                          noise={"bubble_abs": 0.5})
        assert not [f for f in rep.findings if f.rule == "PC302"]

    def test_pc303_ordering_gate(self):
        """The acceptance bar as a named finding: interleaved measuring
        slower than plain 1f1b beyond the band is an error."""
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[2]["ms_per_step"] = 2400.0  # the lockstep-executor regression
        rep = self._check(pc.perf_facts_from_bench(_sweep_line(rows=rows)))
        hits = [f for f in rep.findings if f.rule == "PC303"]
        assert len(hits) == 1
        assert "ordering" in hits[0].message
        assert "1f1b-interleaved" in hits[0].message

    def test_pc303_within_band_is_clean(self):
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[2]["ms_per_step"] = 1900.0  # 2.7% over, inside the 10% band
        rep = self._check(pc.perf_facts_from_bench(_sweep_line(rows=rows)))
        assert not [f for f in rep.findings if f.rule == "PC303"]

    def test_row_ratchet_pc101_names_schedule(self, tmp_path):
        old = pc.perf_facts_from_bench(_sweep_line())
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[1]["ms_per_step"] = 9000.0  # 1f1b regressed ~5x
        new = pc.perf_facts_from_bench(_sweep_line(rows=rows))
        rep = pc.diff_facts(old, new)
        hits = [f for f in rep.findings
                if f.rule == "PC101" and f.location == "1f1b"]
        assert len(hits) == 1 and "schedule sweep" in hits[0].message

    def test_sweep_baseline_round_trip(self, tmp_path):
        facts = pc.perf_facts_from_bench(_sweep_line())
        pc.update_baseline("cpu_schedule_sweep", facts,
                           baselines_dir=tmp_path,
                           noise={"bubble_abs": 0.75})
        rep = pc.check_perf("cpu_schedule_sweep", facts,
                            baselines_dir=tmp_path)
        assert pc.verdict_of(rep) == "clean", rep.format()
        # a justified ordering regression records in-file
        rows = _sweep_line()["schedule_sweep"]["rows"]
        rows[2]["ms_per_step"] = 2400.0
        bad = pc.perf_facts_from_bench(_sweep_line(rows=rows))
        with pytest.raises(pc.PerfContractError, match="PC303"):
            pc.update_baseline("cpu_schedule_sweep", bad,
                               baselines_dir=tmp_path)

    def test_committed_sweep_baseline_exists_and_is_wide_banded(self):
        snap = pc.load_baseline("cpu_schedule_sweep")
        assert snap is not None, \
            "analysis/perf_baselines/cpu_schedule_sweep.json must be committed"
        rows = {r["schedule"]: r
                for r in (snap["facts"].get("schedule_sweep") or [])}
        assert set(rows) >= {"wavefront", "1f1b", "1f1b-interleaved",
                             "1f1b-zb"}
        # the measured ordering IS the committed claim
        assert rows["1f1b-interleaved"]["step_time_ms"] <= \
            rows["1f1b"]["step_time_ms"] * (1 + pc.DEFAULT_NOISE["sweep_order_frac"])
        # CPU lanes time-share host cores: the bubble band must be
        # explicitly widened in-file (the TPU default stays tight)
        assert snap["noise"]["bubble_abs"] > pc.DEFAULT_NOISE["bubble_abs"]
