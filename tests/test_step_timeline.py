"""Pipeline step timelines (telemetry.step_timeline) and the surfaces that
show them (tools/trace_report.py, the planner's calibration audit trail).

Covers the timeline reconstruction on a committed pp=2 fixture (tick
boundaries, per-stage busy/idle, measured bubble fraction, straggler
attribution), the work-compacted executor's fixture, and live CPU-captured
tiny-llama traces for every manual-vjp pipeline schedule carrying measured
bubble fraction + per-stage busy/idle.  All tier-1 / CPU."""

import importlib.util
import json
from pathlib import Path

import pytest

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
        # 1f1b pp=2 nm=4 — the fixture's measurement sits 1/120 under it
        assert p["bubble_fraction_predicted"] == pytest.approx(0.2)
        assert p["bubble_residual"] == pytest.approx(230 / 1200 - 0.2,
                                                     abs=1e-6)

    def test_ticks_per_step_passthrough(self, compacted):
        # the facts' expected tick counts are echoed so a reader can tell
        # compaction from a broken marker chain
        assert compacted["ticks_per_step"]["w_ticks"] == 0
        assert compacted["ticks_per_step"]["head_ticks"] == 4

