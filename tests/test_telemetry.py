"""Unified step telemetry (telemetry/ + trainer wiring): span decomposition,
MFU plumbing per model family, compile census / run_summary.json schema,
recompile detection, goodput accounting, and the dispatch-ahead contract
(zero host syncs between logging boundaries) — all tier-1 / CPU."""

import importlib.util
import json
import logging
import time
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_training_tpu.telemetry import (
    RecompileDetector,
    SpanTimer,
    TelemetryConfig,
)
from neuronx_distributed_training_tpu.models.family import flops_for_model
from neuronx_distributed_training_tpu.utils import perf


# ---------------------------------------------------------------------------
# spans + goodput
# ---------------------------------------------------------------------------


class TestSpanTimer:
    def test_span_decomposition_sums_to_wall(self):
        spans = SpanTimer()
        t0 = time.perf_counter()
        with spans.span("data_wait"):
            time.sleep(0.02)
        with spans.span("dispatch"):
            time.sleep(0.01)
        with spans.span("host_sync"):
            time.sleep(0.01)
        wall = time.perf_counter() - t0
        got = spans.drain()
        assert set(got) == {"data_wait", "dispatch", "host_sync"}
        total = sum(got.values())
        # the spans cover everything but loop overhead: they must sum to
        # within a few ms of the elapsed wall time, and never exceed it
        assert total <= wall + 1e-6
        assert total >= wall - 0.02, (total, wall)
        assert got["data_wait"] >= 0.015

    def test_drain_resets_but_goodput_accumulates(self):
        spans = SpanTimer()
        spans.add("checkpoint", 2.0)
        assert spans.drain() == {"checkpoint": 2.0}
        assert spans.drain() == {}
        spans.add("checkpoint", 1.0)
        assert spans.nonproductive_seconds() == pytest.approx(3.0)

    def test_take_excluded_covers_nonproductive_only(self):
        spans = SpanTimer()
        spans.add("dispatch", 5.0)
        spans.add("validate", 1.5)
        spans.add("compile", 2.0)
        assert spans.take_excluded() == pytest.approx(3.5)
        assert spans.take_excluded() == 0.0  # reset on take
        spans.add("checkpoint", 0.5)
        assert spans.take_excluded() == pytest.approx(0.5)

    def test_goodput_fraction_and_summary(self):
        spans = SpanTimer()
        spans.add("checkpoint", 1.0)
        wall = spans.wall_seconds
        frac = spans.goodput_fraction()
        assert 0.0 <= frac <= 1.0
        s = spans.goodput_summary()
        assert s["nonproductive_seconds"] == pytest.approx(1.0)
        assert s["breakdown_seconds"] == {"checkpoint": 1.0}
        # productive is derived, clamped at zero (here the synthetic 1.0 s of
        # checkpoint exceeds the real ~0 s wall)
        assert s["productive_seconds"] == pytest.approx(
            max(s["wall_seconds"] - s["nonproductive_seconds"], 0.0), abs=1e-6)
        assert wall >= 0.0

    def test_disabled_timer_is_inert(self):
        spans = SpanTimer(enabled=False)
        with spans.span("validate"):
            pass
        spans.add("checkpoint", 9.0)
        assert spans.drain() == {}
        assert spans.take_excluded() == 0.0
        assert spans.goodput_fraction() == pytest.approx(1.0, abs=1e-3)


# ---------------------------------------------------------------------------
# recompile / retrace detection
# ---------------------------------------------------------------------------


class TestRecompileDetector:
    def test_fires_on_forced_shape_change_with_diff(self, caplog):
        det = RecompileDetector()
        b1 = {"input_ids": np.zeros((8, 32), np.int32)}
        b2 = {"input_ids": np.zeros((5, 32), np.int32)}  # ragged final batch
        assert det.check("train_step", b1) is False
        assert det.check("train_step", b1) is False  # stable: no event
        with caplog.at_level(
                logging.WARNING,
                logger="neuronx_distributed_training_tpu.telemetry.recompile"):
            assert det.check("train_step", b2) is True
        assert det.events and "train_step" in det.events[0]
        msg = caplog.records[-1].message
        assert "8,32" in msg and "5,32" in msg, msg

    def test_structure_change_reports_added_leaf(self):
        det = RecompileDetector()
        det.check("f", {"a": np.zeros((2,), np.float32)})
        assert det.check("f", {"a": np.zeros((2,), np.float32),
                               "b": np.zeros((3,), np.float32)}) is True
        assert "added" in det.events[-1]

    def test_independent_names(self):
        det = RecompileDetector()
        det.check("train", {"x": np.zeros((4,), np.float32)})
        # a different fn with different shapes is NOT a retrace of the first
        assert det.check("eval", {"x": np.zeros((2,), np.float32)}) is False


# ---------------------------------------------------------------------------
# Throughput warm-up + tokens_per_sec (one source of truth for MFU)
# ---------------------------------------------------------------------------


class TestThroughput:
    def test_peak_waits_for_min_samples(self):
        t = perf.Throughput(batch_size=8, window=10)
        # a one-off fast first window must not pin a phantom peak
        t.update(0.001)
        assert t.peak == 0.0
        t.update(1.0)
        assert t.peak == 0.0
        t.update(1.0)  # 3rd sample: window is representative now
        assert t.peak > 0.0

    def test_small_window_records_immediately(self):
        t = perf.Throughput(batch_size=8, window=1)
        t.update(1.0)
        assert t.peak == pytest.approx(8.0)

    def test_tokens_per_sec_derives_from_seq_len(self):
        t = perf.Throughput(batch_size=4, window=10, seq_len=32)
        assert t.tokens_per_sec == 0.0
        rate = t.update(2.0)  # 4 seqs / 2 s = 2 seq/s
        assert rate == pytest.approx(2.0)
        assert t.last == pytest.approx(2.0)
        assert t.tokens_per_sec == pytest.approx(2.0 * 32)


# ---------------------------------------------------------------------------
# per-family analytic FLOPs (the MFU numerator)
# ---------------------------------------------------------------------------


class TestFlopsForModel:
    def _llama(self, **kw):
        from neuronx_distributed_training_tpu.models import llama

        base = dict(vocab_size=1024, hidden_size=64, intermediate_size=128,
                    num_layers=4, num_attention_heads=4, num_kv_heads=2,
                    max_position_embeddings=64)
        base.update(kw)
        return llama.LlamaConfig(**base)

    def test_llama_matches_flops_for_config(self):
        cfg = self._llama()
        assert flops_for_model(cfg, 64) == perf.flops_for_config(cfg, 64)
        assert flops_for_model(cfg, 64) > 0

    def test_mixtral_counts_activated_experts_only(self):
        from neuronx_distributed_training_tpu.models import mixtral
        from neuronx_distributed_training_tpu.ops.moe import MoEConfig

        mk = lambda k: mixtral.MixtralConfig(
            llama=self._llama(), moe=MoEConfig(num_experts=8, top_k=k))
        f1, f2 = flops_for_model(mk(1), 64), flops_for_model(mk(2), 64)
        assert f2 > f1 > 0
        # top_k=2 adds exactly one more expert's SwiGLU per MoE layer
        swiglu = 2 * 64 * 3 * 128
        assert f2 - f1 == pytest.approx(4 * swiglu)
        # dense llama vs top_k=1 mixtral differ only by the router matmul
        dense = flops_for_model(self._llama(), 64)
        router = 2 * 64 * 8
        assert f1 - dense == pytest.approx(4 * router)

    def test_gpt_glu_vs_plain_activation(self):
        from neuronx_distributed_training_tpu.models import gpt

        mk = lambda act: gpt.GPTConfig(
            vocab_size=1024, hidden_size=64, ffn_hidden_size=128,
            num_layers=4, num_attention_heads=4, activation=act)
        plain, glu = (flops_for_model(mk("gelu"), 64),
                      flops_for_model(mk("swiglu"), 64))
        # GLU runs 3 MLP matmuls to plain's 2 at equal ffn width
        mlp2 = 4 * 2 * 64 * 2 * 128
        assert glu - plain == pytest.approx(mlp2 / 2)
        assert plain > 0

    def test_gpt_moe(self):
        from neuronx_distributed_training_tpu.models import gpt
        from neuronx_distributed_training_tpu.ops.moe import MoEConfig

        dense = gpt.GPTConfig(vocab_size=1024, hidden_size=64,
                              num_layers=4, num_attention_heads=4)
        moe = gpt.GPTConfig(vocab_size=1024, hidden_size=64,
                            num_layers=4, num_attention_heads=4,
                            moe=MoEConfig(num_experts=4, top_k=2))
        assert flops_for_model(moe, 64) > flops_for_model(dense, 64)


# ---------------------------------------------------------------------------
# exp_manager.telemetry config validation / round-trip
# ---------------------------------------------------------------------------


class TestTelemetryConfig:
    def test_defaults(self):
        tc = TelemetryConfig.from_config(None)
        assert tc.spans and tc.mfu and tc.compile_census and tc.goodput
        assert not tc.device_memory  # the one backend-query knob is opt-in

    def test_unknown_key_rejected_at_load(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = {"exp_manager": {"telemetry": {"spanz": True}},
               "data": {"global_batch_size": 8, "micro_batch_size": 1}}
        with pytest.raises(ValueError, match="spanz"):
            load_config(cfg)

    def test_non_bool_rejected(self):
        with pytest.raises(ValueError, match="boolean"):
            TelemetryConfig.from_config({"mfu": "yes"})

    def test_blanket_off(self):
        tc = TelemetryConfig.from_config(False)
        assert not (tc.spans or tc.mfu or tc.compile_census or tc.goodput
                    or tc.device_memory)

    def test_round_trip_through_exp_manager(self, tmp_path):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.exp_manager import ExpManager

        cfg = load_config({
            "exp_manager": {"exp_dir": str(tmp_path), "log_files": False,
                            "create_tensorboard_logger": False,
                            "telemetry": {"device_memory": True,
                                          "goodput": False}},
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 64},
        })
        exp = ExpManager.from_config(cfg, global_batch_size=8)
        assert exp.telemetry.device_memory is True
        assert exp.telemetry.goodput is False
        assert exp.telemetry.spans is True  # unmentioned knob keeps default
        assert exp.throughput.seq_len == 64
        exp.close()


# ---------------------------------------------------------------------------
# step_timed decontamination + MFU logging (ExpManager level)
# ---------------------------------------------------------------------------


class TestExpManagerTelemetry:
    def _exp(self, tmp_path, **kw):
        from neuronx_distributed_training_tpu.trainer.exp_manager import ExpManager

        return ExpManager(exp_dir=str(tmp_path), log_files=False,
                          create_tensorboard_logger=False, **kw)

    def test_step_timed_excludes_nonproductive_wall(self, tmp_path, monkeypatch):
        from neuronx_distributed_training_tpu.trainer import exp_manager as em

        clock = {"t": 100.0}
        monkeypatch.setattr(em.time, "perf_counter", lambda: clock["t"])
        exp = self._exp(tmp_path, global_batch_size=8)
        exp.step_timed()  # arm
        clock["t"] = 110.0
        # 10 s window over 2 steps, 6 s of it checkpoint/validate stall:
        # per-step time must be (10 - 6) / 2, not 5
        dt = exp.step_timed(2, exclude_seconds=6.0)
        assert dt == pytest.approx(2.0)
        assert exp.throughput.last == pytest.approx(8.0 / 2.0)
        exp.close()

    def test_mfu_logged_from_single_source_of_truth(self, tmp_path):
        exp = self._exp(tmp_path, global_batch_size=4, seq_len=128,
                        log_every_n_steps=1)
        exp.set_mfu_reference(train_step_flops_per_token=6e6, n_chips=2,
                              peak_tflops_per_chip=0.5)
        exp.step_timed()
        time.sleep(0.01)
        exp.step_timed(1)
        exp.log_metrics(1, {"loss": 1.0})
        exp.close()
        rec = json.loads(
            (exp.log_dir / "metrics.jsonl").read_text().strip().splitlines()[-1])
        assert rec["tokens_per_sec_per_chip"] == pytest.approx(
            exp.throughput.tokens_per_sec / 2)
        assert rec["mfu"] == pytest.approx(
            rec["tokens_per_sec_per_chip"] * 6e6 / 0.5e12)

    def test_run_summary_merges_sections(self, tmp_path):
        exp = self._exp(tmp_path)
        exp.write_run_summary({"compile_seconds": 1.5})
        exp.write_run_summary({"goodput": {"goodput_fraction": 0.9}})
        got = json.loads((exp.log_dir / "run_summary.json").read_text())
        assert got["compile_seconds"] == 1.5
        assert got["goodput"]["goodput_fraction"] == 0.9
        exp.close()


# ---------------------------------------------------------------------------
# trainer integration: the CPU smoke run of the acceptance criteria
# ---------------------------------------------------------------------------


def _tiny_cfg(tmp_path, **over):
    from neuronx_distributed_training_tpu.config.loader import load_config

    cfg = {
        "name": "tel", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": 3, "log_every_n_steps": 1,
                    "val_check_interval": 3, "limit_val_batches": 1},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False},
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
    }
    cfg.update(over)
    return load_config(cfg)


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory, devices8):
    """One tiny fit() with full telemetry; shared across schema assertions."""
    from neuronx_distributed_training_tpu.data import SyntheticDataModule
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    tmp_path = tmp_path_factory.mktemp("telemetry_run")
    cfg = _tiny_cfg(tmp_path)
    val = SyntheticDataModule(vocab_size=128, seq_len=32,
                              global_batch_size=8, seed=9)
    t = Trainer.from_config(cfg, val_data_module=val,
                            enable_checkpointing=False)
    metrics = t.fit()
    exp_dir = tmp_path / "exp" / "tel" / "version_0"
    records = [json.loads(l) for l in
               (exp_dir / "metrics.jsonl").read_text().strip().splitlines()]
    summary = json.loads((exp_dir / "run_summary.json").read_text())
    return t, metrics, records, summary


class TestTrainerTelemetry:
    def test_metrics_jsonl_schema(self, telemetry_run):
        _, metrics, records, _ = telemetry_run
        boundary = [r for r in records if "step_time" in r]
        assert boundary, records
        last = boundary[-1]
        for key in ("tokens_per_sec_per_chip", "goodput_fraction",
                    "time/data_wait", "time/dispatch", "time/host_sync",
                    "throughput_seqs_per_sec", "loss", "lr"):
            assert key in last, (key, sorted(last))
        assert 0.0 <= last["goodput_fraction"] <= 1.0
        assert last["tokens_per_sec_per_chip"] > 0.0
        # a CPU has no peak to be held against: MFU is a device metric and
        # is not emitted off the TPU
        assert "mfu" not in last
        assert np.isfinite(metrics["val_loss"])

    def test_first_boundary_carries_compile_span(self, telemetry_run):
        _, _, records, _ = telemetry_run
        first = next(r for r in records if "step_time" in r)
        assert first.get("time/compile", 0.0) > 0.0

    def test_run_summary_census(self, telemetry_run):
        _, _, _, summary = telemetry_run
        assert summary["compile_seconds"] > 0.0
        coll = summary["collectives"]
        assert set(coll) == {"all-reduce", "all-gather", "reduce-scatter",
                             "collective-permute", "all-to-all"}
        assert sum(coll.values()) > 0  # tp=2 + sp inserts real collectives
        mem = summary["memory_analysis"]
        assert mem["peak_bytes"] > 0
        assert {"temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes"} <= set(mem)
        # the analytic FLOPs model the MFU derives from, both conventions
        assert summary["train_step_flops_per_token"] == pytest.approx(
            3.0 * summary["fwd_flops_per_token"])
        assert summary["model_family"] == "LlamaConfig"
        assert summary["n_chips"] == 8
        assert summary["seq_len"] == 32

    def test_goodput_summary_written(self, telemetry_run):
        _, _, _, summary = telemetry_run
        gp = summary["goodput"]
        assert 0.0 <= gp["goodput_fraction"] <= 1.0
        assert gp["productive_seconds"] + gp["nonproductive_seconds"] == (
            pytest.approx(gp["wall_seconds"], rel=0.05))
        assert "compile" in gp["breakdown_seconds"]

    def test_census_swapped_in_aot_executable(self, telemetry_run):
        # the census AOT-compiles once and the loop runs THAT executable:
        # no .lower means no second (jit-cache) compile ever happened
        t, _, _, _ = telemetry_run
        assert not hasattr(t.train_step, "lower")

    def test_step_time_excludes_compile(self, telemetry_run):
        # the old step_timed folded the first compile into the first window;
        # now the first boundary's step_time must be of the same order as
        # later steady-state steps, not compile-sized
        _, _, records, summary = telemetry_run
        boundary = [r for r in records if "step_time" in r]
        assert boundary[0]["step_time"] < summary["compile_seconds"]


class TestCensusOffCompileClassification:
    def test_first_jit_dispatch_counts_as_compile(self, tmp_path, devices8):
        """With compile_census off the first jit call traces+compiles inline;
        that wall time must land in time/compile (excluded from throughput
        and goodput), not in productive dispatch — the knob interaction must
        not silently reintroduce the contamination this PR removes."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _tiny_cfg(
            tmp_path,
            exp_manager={"exp_dir": str(tmp_path / "exp"),
                         "create_tensorboard_logger": False,
                         "log_files": False,
                         "telemetry": {"compile_census": False}},
        )
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        t.fit()
        assert hasattr(t.train_step, "lower")  # census off: still the jit fn
        exp_dir = tmp_path / "exp" / "tel" / "version_0"
        records = [json.loads(l) for l in
                   (exp_dir / "metrics.jsonl").read_text().strip().splitlines()]
        assert not (exp_dir / "run_summary.json").exists() or \
            "collectives" not in json.loads(
                (exp_dir / "run_summary.json").read_text())
        boundary = [r for r in records if "step_time" in r]
        first = boundary[0]
        assert first.get("time/compile", 0.0) > 0.0
        # compile dominates the first window; step_time must not absorb it
        assert first["step_time"] < first["time/compile"]


class TestDispatchAheadContract:
    def test_no_host_sync_between_boundaries(self, tmp_path, devices8):
        """Telemetry must add ZERO host syncs between logging boundaries:
        with an instrumented step, metric values are only ever converted to
        host floats at boundary steps."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _tiny_cfg(
            tmp_path,
            trainer={"max_steps": 6, "log_every_n_steps": 3},
        )
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
            # pure host-side stand-in: any float() of its metrics IS a sync
            return real_params, real_opt, {"loss": _Scalar(t.step),
                                           "grad_norm": _Scalar(t.step)}

        t.train_step = fake_step
        t.fit()
        # metrics were fetched only at the boundary steps (pre-increment
        # step ids 2 and 5 -> boundaries at steps 3 and 6)
        assert conversions, "boundaries must fetch metrics"
        assert set(conversions) == {2, 5}, conversions


# ---------------------------------------------------------------------------
# tools/metrics_report.py smoke
# ---------------------------------------------------------------------------


def _load_metrics_report():
    path = Path(__file__).resolve().parents[1] / "tools" / "metrics_report.py"
    spec = importlib.util.spec_from_file_location("metrics_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMetricsReport:
    def test_renders_run_dir(self, tmp_path, capsys):
        mr = _load_metrics_report()
        with open(tmp_path / "metrics.jsonl", "w") as f:
            for s in (2, 4):
                f.write(json.dumps({"step": s, "loss": 7.0 - s, "mfu": 0.5,
                                    "goodput_fraction": 0.9}) + "\n")
        with open(tmp_path / "run_summary.json", "w") as f:
            json.dump({"compile_seconds": 3.0,
                       "collectives": {"all-reduce": 2},
                       "memory_analysis": {"peak_bytes": 2048},
                       "goodput": {"goodput_fraction": 0.91,
                                   "wall_seconds": 10.0,
                                   "breakdown_seconds": {"compile": 0.9}}}, f)
        assert mr.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        for needle in ("mfu", "goodput_fraction", "steps 2..4",
                       "compile_seconds", "all-reduce=2", "2.0 KiB",
                       "goodput"):
            assert needle in out, (needle, out)

    def test_missing_path_errors(self, tmp_path):
        mr = _load_metrics_report()
        assert mr.main([str(tmp_path / "nope")]) == 2

    def test_renders_real_run_output(self, telemetry_run, tmp_path, capsys):
        # the renderer must accept exactly what the trainer writes
        mr = _load_metrics_report()
        t, _, _, _ = telemetry_run
        assert mr.main([str(t.exp.log_dir)]) == 0
        out = capsys.readouterr().out
        assert "tokens_per_sec_per_chip" in out and "compile census" in out
