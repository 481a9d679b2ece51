"""Memory observability (telemetry.memory): pprof parsing + attribution,
knob validation, boundary sampling, the OOM drill, planner HBM calibration,
and the live tiny-llama fit() smoke.

Run ``python tests/test_memory.py --regen-fixture`` to regenerate the
committed pprof fixture after changing the generator below — the
``test_fixture_committed_and_current`` ratchet fails otherwise.
"""

from __future__ import annotations

import gc
import gzip
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from neuronx_distributed_training_tpu.telemetry.memory import (
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

FIXTURE = Path(__file__).parent / "data" / "memory_profile_fixture.pprof"


# ---------------------------------------------------------------------------
# a tiny pprof ENCODER (protobuf wire format, stdlib-only) — the fixture
# generator, and the per-test profile builder
# ---------------------------------------------------------------------------


def _enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field_varint(field: int, value: int) -> bytes:
    return _enc_varint(field << 3) + _enc_varint(value)


def _field_bytes(field: int, payload: bytes) -> bytes:
    return _enc_varint((field << 3) | 2) + _enc_varint(len(payload)) + payload


def _packed(field: int, values: list[int]) -> bytes:
    return _field_bytes(field, b"".join(_enc_varint(v) for v in values))


class PprofBuilder:
    """Build a pprof Profile protobuf the way jax's memory profiler does:
    sample_type [(allocations, count), (space, bytes)], packed sample
    values, leaf-first location chains, kind/device labels."""

    def __init__(self) -> None:
        self.strings: list[str] = [""]
        self._functions: dict[tuple[str, str], int] = {}
        self._locations: dict[tuple[int, ...], int] = {}
        self.samples: list[bytes] = []

    def sid(self, s: str) -> int:
        try:
            return self.strings.index(s)
        except ValueError:
            self.strings.append(s)
            return len(self.strings) - 1

    def func(self, name: str, filename: str = "test.py") -> int:
        key = (name, filename)
        if key not in self._functions:
            self._functions[key] = len(self._functions) + 1
        return self._functions[key]

    def loc(self, frames: list[tuple[str, str]]) -> int:
        fids = tuple(self.func(n, f) for n, f in frames)
        if fids not in self._locations:
            self._locations[fids] = len(self._locations) + 1
        return self._locations[fids]

    def add(self, nbytes: int, count: int, stack: list, *,
            kind: str = "buffer", device: str = "TPU_0") -> None:
        """``stack``: leaf-first ``[(fn, filename), ...]`` (a bare str means
        filename "test.py")."""
        frames = [(s, "test.py") if isinstance(s, str) else tuple(s)
                  for s in stack]
        loc_ids = [self.loc([fr]) for fr in frames]
        labels = b""
        for key, val in (("kind", kind), ("device", device)):
            if val is not None:
                labels += _field_bytes(3, _field_varint(1, self.sid(key))
                                       + _field_varint(2, self.sid(val)))
        self.samples.append(
            _packed(1, loc_ids) + _packed(2, [count, nbytes]) + labels)

    def build(self, *, gzipped: bool = True) -> bytes:
        out = b""
        for t, u in (("allocations", "count"), ("space", "bytes")):
            out += _field_bytes(1, _field_varint(1, self.sid(t))
                                + _field_varint(2, self.sid(u)))
        for s in self.samples:
            out += _field_bytes(2, s)
        for fids, lid in self._locations.items():
            body = _field_varint(1, lid)
            for fid in fids:
                body += _field_bytes(4, _field_varint(1, fid))
            out += _field_bytes(4, body)
        for (name, filename), fid in self._functions.items():
            out += _field_bytes(5, _field_varint(1, fid)
                                + _field_varint(2, self.sid(name))
                                + _field_varint(4, self.sid(filename)))
        for s in self.strings:
            out += _field_bytes(6, s.encode())
        return gzip.compress(out, 9, mtime=0) if gzipped else out


def build_fixture_bytes() -> bytes:
    """The committed fixture: two devices, every attribution class, a
    dispatch pool, and an unattributed mystery — all totals hand-checkable:

    ===========  ======  ======  =========================================
    class        TPU_0   TPU_1   stack / label
    ===========  ======  ======  =========================================
    params        1000    1000   init_params
    opt_state     2000    2000   init_opt_state
    chunk_store    500       -   stage_loop @ parallel/pipeline.py
    moe_workspace    -     300   moe_dropless
    batch          100     100   _batched_device_put_impl
    (dispatch)    4000    3600   cache_miss <- <module>   [-> activations]
    executable     700       -   kind=executable
    unattributed   250       -   mystery_allocator
    ===========  ======  ======  =========================================

    Totals: TPU_0 = 8550, TPU_1 = 7000, all = 15550.
    """
    b = PprofBuilder()
    for dev, nbytes in (("TPU_0", 1000), ("TPU_1", 1000)):
        b.add(nbytes, 2, ["broadcast", "init_params", "cache_miss"],
              device=dev)
    for dev, nbytes in (("TPU_0", 2000), ("TPU_1", 2000)):
        b.add(nbytes, 3, ["zeros", "init_opt_state", "cache_miss"],
              device=dev)
    b.add(500, 1, [("stage_loop",
                    "neuronx_distributed_training_tpu/parallel/pipeline.py")],
          device="TPU_0")
    b.add(300, 1, ["moe_dropless"], device="TPU_1")
    b.add(100, 1, ["_batched_device_put_impl"], device="TPU_0")
    b.add(100, 1, ["_batched_device_put_impl"], device="TPU_1")
    b.add(4000, 8, ["cache_miss", "<module>"], device="TPU_0")
    b.add(3600, 7, ["cache_miss", "<module>"], device="TPU_1")
    b.add(700, 1, ["compile"], kind="executable", device="TPU_0")
    b.add(250, 1, ["mystery_allocator"], device="TPU_0")
    return b.build()


#: the fixture's hand-computed invariants
FIXTURE_TOTAL = 15550
FIXTURE_BY_DEVICE = {"TPU_0": 8550, "TPU_1": 7000}
FIXTURE_ATTRIBUTION_NO_HINTS = {
    "params": 2000, "opt_state": 4000, "chunk_store": 500,
    "moe_workspace": 300, "batch": 200, "activations": 7600,
    "executable": 700, "unattributed": 250,
}


# ---------------------------------------------------------------------------
# parsing + attribution
# ---------------------------------------------------------------------------


class TestParsePprof:
    def test_fixture_committed_and_current(self):
        """The ratchet: the committed fixture must match the generator —
        regenerate with ``python tests/test_memory.py --regen-fixture``."""
        assert FIXTURE.exists(), \
            "fixture missing: python tests/test_memory.py --regen-fixture"
        # compare the profile, not the gzip container: the header's OS byte
        # differs between Python versions (3.12 writes 255, "unknown")
        assert gzip.decompress(FIXTURE.read_bytes()) \
            == gzip.decompress(build_fixture_bytes())

    def test_totals_and_devices(self):
        prof = parse_memory_profile(FIXTURE.read_bytes())
        assert prof["total_bytes"] == FIXTURE_TOTAL
        assert prof["by_device"] == FIXTURE_BY_DEVICE

    def test_gzip_and_raw_parse_identically(self):
        b = PprofBuilder()
        b.add(123, 1, ["f"])
        raw = b.build(gzipped=False)
        gz = gzip.compress(raw)
        assert parse_memory_profile(raw) == parse_memory_profile(gz)

    def test_stack_and_labels(self):
        prof = parse_memory_profile(FIXTURE.read_bytes())
        execs = [s for s in prof["samples"]
                 if s["labels"].get("kind") == "executable"]
        assert len(execs) == 1 and execs[0]["bytes"] == 700
        params = [s for s in prof["samples"] if "init_params" in s["stack"]]
        assert len(params) == 2
        assert all(s["labels"]["device"] in ("TPU_0", "TPU_1")
                   for s in prof["samples"])

    def test_value_columns_selected_by_name(self):
        # swap the sample_type order: bytes first, count second — the
        # parser must follow the names, not the conventional positions
        b = PprofBuilder()
        b.sid("space"), b.sid("bytes"), b.sid("allocations"), b.sid("count")
        body = b""
        for t, u in (("space", "bytes"), ("allocations", "count")):
            body += _field_bytes(1, _field_varint(1, b.sid(t))
                                 + _field_varint(2, b.sid(u)))
        lid = b.loc([("f", "test.py")])
        body += _field_bytes(2, _packed(1, [lid]) + _packed(2, [999, 4]))
        for fids, loc_id in b._locations.items():
            lb = _field_varint(1, loc_id)
            for fid in fids:
                lb += _field_bytes(4, _field_varint(1, fid))
            body += _field_bytes(4, lb)
        for (name, filename), fid in b._functions.items():
            body += _field_bytes(5, _field_varint(1, fid)
                                 + _field_varint(2, b.sid(name))
                                 + _field_varint(4, b.sid(filename)))
        for s in b.strings:
            body += _field_bytes(6, s.encode())
        prof = parse_memory_profile(body)
        assert prof["total_bytes"] == 999
        assert prof["total_count"] == 4

    def test_live_cpu_profile_parses(self):
        import jax.numpy as jnp

        keep = jnp.ones((64, 64))  # noqa: F841 — a live buffer to find
        prof = parse_memory_profile(jax.profiler.device_memory_profile())
        assert prof["total_bytes"] > 0
        assert prof["samples"]
        att = attribute_profile(prof)
        assert sum(r["bytes"] for r in att.values()) == prof["total_bytes"]


class TestAttribution:
    def test_fixture_attribution_no_hints(self):
        prof = parse_memory_profile(FIXTURE.read_bytes())
        att = attribute_profile(prof)
        got = {cls: rec["bytes"] for cls, rec in att.items()
               if rec["bytes"]}
        assert got == FIXTURE_ATTRIBUTION_NO_HINTS

    def test_partition_reconciles_exactly(self):
        prof = parse_memory_profile(FIXTURE.read_bytes())
        att = attribute_profile(prof)
        assert sum(r["bytes"] for r in att.values()) == FIXTURE_TOTAL
        assert sum(r["count"] for r in att.values()) == prof["total_count"]

    def test_tree_join_carves_dispatch_pool(self):
        """The donation-erased dispatch pool splits by the EXACT tree
        sizes: params tops up 2000->2500, opt_state 4000->9000, master
        takes 1000, and what's left (1100) is honest activations."""
        prof = parse_memory_profile(FIXTURE.read_bytes())
        att = attribute_profile(prof, {"params": 2500, "opt_state": 9000,
                                       "master": 1000})
        assert att["params"]["bytes"] == 2500
        assert att["opt_state"]["bytes"] == 9000
        assert att["master"]["bytes"] == 1000
        assert att["activations"]["bytes"] == 7600 - 500 - 5000 - 1000
        assert sum(r["bytes"] for r in att.values()) == FIXTURE_TOTAL

    def test_tree_join_never_goes_negative(self):
        # hints larger than the pool: carve caps at the pool, the total
        # still reconciles (nothing is invented)
        prof = parse_memory_profile(FIXTURE.read_bytes())
        att = attribute_profile(prof, {"params": 10**9})
        assert sum(r["bytes"] for r in att.values()) == FIXTURE_TOTAL
        assert att["activations"]["bytes"] == 0

    def test_unattributed_never_dropped(self):
        prof = parse_memory_profile(FIXTURE.read_bytes())
        att = attribute_profile(prof, {"params": 10**9})
        assert att["unattributed"]["bytes"] == 250


# ---------------------------------------------------------------------------
# allocator sampling + metrics
# ---------------------------------------------------------------------------


class _FakeDev:
    def __init__(self, i, in_use, peak=None, limit=None, fail=False):
        self.id = i
        self.device_kind = "fake"
        self._stats = {"bytes_in_use": in_use}
        if peak is not None:
            self._stats["peak_bytes_in_use"] = peak
        if limit is not None:
            self._stats["bytes_limit"] = limit
        self._fail = fail

    def memory_stats(self):
        if self._fail:
            raise RuntimeError("no stats")
        return self._stats


class TestSampling:
    def test_samples_skip_unimplemented(self):
        devs = [_FakeDev(0, 100), _FakeDev(1, 0, fail=True)]
        s = device_memory_samples(devs)
        assert [d["device"] for d in s] == ["0"]

    def test_metrics_spread_and_peak_device(self):
        devs = [_FakeDev(0, 100, peak=150, limit=1000),
                _FakeDev(1, 900, peak=950, limit=1000),
                _FakeDev(2, 400, peak=500, limit=1000)]
        m = memory_metrics(device_memory_samples(devs))
        assert m["memory/bytes_in_use_max"] == 900
        assert m["memory/bytes_in_use_min"] == 100
        assert m["memory/bytes_in_use_p50"] == 400
        assert m["memory/peak_bytes_max"] == 950
        assert m["memory/peak_device"] == 1.0
        # headroom is the WORST device's: 1 - 900/1000
        assert m["memory/hbm_headroom_fraction"] == pytest.approx(0.1)

    def test_metrics_empty_without_stats(self):
        assert memory_metrics([]) == {}
        assert device_memory_samples(jax.devices()[:1]) == []  # CPU: None

    def test_loop_device_memory_metrics_multi_device(self, cpu_mesh,
                                                     monkeypatch):
        """The satellite: _device_memory_metrics must cover every local
        device (max/min/p50 + the named peak device), not just flat[0]."""
        from neuronx_distributed_training_tpu.trainer import loop as L

        fakes = [_FakeDev(i, 100 * (i + 1), peak=200 * (i + 1), limit=10000)
                 for i in range(4)]
        monkeypatch.setattr(L, "_local_mesh_devices", lambda mesh: fakes)
        m = L._device_memory_metrics(cpu_mesh)
        assert m["device_bytes_in_use"] == 400       # the WORST device
        assert m["device_bytes_in_use_min"] == 100
        assert m["device_bytes_in_use_p50"] == 300
        assert m["device_peak_bytes_in_use"] == 800
        assert m["device_peak_device"] == 3.0        # named by index
        assert m["device_bytes_limit"] == 10000


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------


class TestKnobs:
    def test_defaults_disabled(self):
        cfg = MemoryConfig.from_config(None)
        assert cfg.enabled is False and cfg.profile is True

    def test_bool_form(self):
        assert MemoryConfig.from_config(True).enabled is True

    def test_unknown_key_did_you_mean(self):
        with pytest.raises(ValueError, match="did you mean"):
            MemoryConfig.from_config({"enabeld": True})

    def test_non_bool_rejected(self):
        with pytest.raises(ValueError, match="must be a boolean"):
            MemoryConfig.from_config({"profile": "yes"})

    @pytest.mark.parametrize("block, msg", [
        ({"start_step": -1}, "start_step"),
        ({"num_steps": 0}, "num_steps"),
        ({"headroom_alert_fraction": 1.5}, "headroom_alert_fraction"),
    ])
    def test_range_validation(self, block, msg):
        with pytest.raises(ValueError, match=msg):
            MemoryConfig.from_config(block)

    def test_telemetry_config_wiring(self):
        from neuronx_distributed_training_tpu.telemetry import (
            TelemetryConfig,
        )

        tc = TelemetryConfig.from_config(
            {"memory": {"enabled": True, "num_steps": 5}})
        assert tc.memory.enabled and tc.memory.num_steps == 5
        with pytest.raises(ValueError, match="memory"):
            TelemetryConfig.from_config({"memory": {"strat_step": 2}})

    def test_load_config_path(self, tmp_path):
        from neuronx_distributed_training_tpu.config.loader import (
            load_config,
        )

        cfg = load_config({
            "name": "x",
            "exp_manager": {"telemetry": {"memory": {"enabled": True}}},
            "model": {"vocab_size": 64, "hidden_size": 32,
                      "num_layers": 1, "num_attention_heads": 2},
            "data": {"seq_length": 16, "global_batch_size": 2,
                     "synthetic": True},
        })
        from neuronx_distributed_training_tpu.telemetry import (
            TelemetryConfig,
        )

        tc = TelemetryConfig.from_config(
            cfg["exp_manager"]["telemetry"])
        assert tc.memory.enabled

    def test_is_oom_error(self):
        assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert is_oom_error(MemoryError("Out of memory allocating 1G"))
        assert not is_oom_error(ValueError("shape mismatch"))


# ---------------------------------------------------------------------------
# the plane: windowing + summary + OOM bundle (fake devices)
# ---------------------------------------------------------------------------


class TestMemoryPlane:
    def _plane(self, tmp_path, **kw):
        devs = [_FakeDev(0, 500, peak=600, limit=2000),
                _FakeDev(1, 900, peak=1000, limit=2000)]
        cfg = MemoryConfig(enabled=True, start_step=1, num_steps=2,
                           **kw.pop("cfg_kw", {}))
        return MemoryPlane(cfg, tmp_path, devices=devs, **kw), devs

    def test_window_captures_and_writes_summary(self, tmp_path):
        plane, _ = self._plane(tmp_path)
        m0 = plane.boundary(0)     # before the window
        assert not plane.profiled and "memory/bytes_in_use_max" in m0
        plane.boundary(1)          # in-window capture
        plane.boundary(2)          # in-window capture (max kept)
        assert not plane.profiled  # window still open
        plane.boundary(3)          # past the window: finalize
        assert plane.profiled
        s = json.loads((tmp_path / "memory_summary.json").read_text())
        assert s["schema"] == 1
        assert s["window"] == {"start_step": 1, "num_steps": 2}
        assert 1 <= s["profiled_step"] < 3
        total = s["profile"]["total_bytes"]
        assert sum(r["bytes"] for r in s["attribution"].values()) == total

    def test_boundary_metrics_and_running_peak(self, tmp_path):
        plane, devs = self._plane(tmp_path)
        m = plane.boundary(0)
        assert m["memory/peak_hbm_bytes"] == 1000.0
        devs[1]._stats["peak_bytes_in_use"] = 1500
        m = plane.boundary(1)
        assert m["memory/peak_hbm_bytes"] == 1500.0
        assert m["memory/hbm_headroom_fraction"] == pytest.approx(0.55)

    def test_close_finalizes_short_run(self, tmp_path):
        plane, _ = self._plane(tmp_path)
        plane.boundary(1)
        plane.close()
        assert (tmp_path / "memory_summary.json").exists()

    def test_run_summary_mirror(self, tmp_path):
        written = {}
        plane, _ = self._plane(tmp_path, write_run_summary=written.update,
                               predicted={"total": 12345.0})
        plane.boundary(1)
        plane.boundary(5)
        assert "memory" in written
        assert written["memory"]["predicted_hbm_bytes"] == 12345.0
        assert written["memory"]["attribution"]

    def test_headroom_alert_warns_once(self, tmp_path, caplog):
        plane, _ = self._plane(
            tmp_path, cfg_kw={"headroom_alert_fraction": 0.9})
        import logging

        with caplog.at_level(logging.WARNING,
                             logger="neuronx_distributed_training_tpu"
                                    ".telemetry.memory"):
            plane.boundary(0)
            plane.boundary(1)
        warns = [r for r in caplog.records if "headroom" in r.message]
        assert len(warns) == 1
        assert "device 1" in warns[0].getMessage()  # the WORST device named

    def test_headroom_alert_names_limit_reporting_device(self, tmp_path,
                                                         caplog):
        """A device without a bytes_limit must never be named in the
        OOM-proximity warning — only limit-reporting devices rank."""
        devs = [_FakeDev(0, 10**9),                       # no limit
                _FakeDev(1, 950, peak=960, limit=1000)]   # the real risk
        plane = MemoryPlane(
            MemoryConfig(enabled=True, headroom_alert_fraction=0.5,
                         profile=False),
            tmp_path, devices=devs)
        import logging

        with caplog.at_level(logging.WARNING,
                             logger="neuronx_distributed_training_tpu"
                                    ".telemetry.memory"):
            plane.boundary(0)
        warns = [r for r in caplog.records if "headroom" in r.message]
        assert len(warns) == 1
        assert "device 1" in warns[0].getMessage()

    def test_dump_oom_bundle_anatomy(self, tmp_path):
        written = {}
        plane, _ = self._plane(
            tmp_path, write_run_summary=written.update,
            predicted={"params": 10.0, "total": 99.0},
            run_facts={"model_family": "LlamaConfig"})
        plane.boundary(0)
        plane.boundary(1)
        bundle = plane.dump_oom(
            7, RuntimeError("RESOURCE_EXHAUSTED: oom"),
            boundary_metrics={"loss": 1.0},
            memory_analysis={"peak_bytes": 4096})
        assert bundle == tmp_path / "oom_00000007"
        doc = json.loads((bundle / "oom.json").read_text())
        assert doc["kind"] == "oom" and doc["step"] == 7
        assert "RESOURCE_EXHAUSTED" in doc["error"]
        assert doc["predicted_hbm_breakdown"]["total"] == 99.0
        assert doc["memory_analysis"]["peak_bytes"] == 4096
        assert doc["attribution_at_death"]  # fresh capture (CPU allocator)
        ring = json.loads((bundle / "samples.json").read_text())
        assert [r["step"] for r in ring] == [0, 1]
        assert written["oom"]["bundle"] == "oom_00000007"
        # at most one per process
        assert plane.dump_oom(8, RuntimeError("RESOURCE_EXHAUSTED")) is None

    def test_disabled_plane_is_inert(self, tmp_path):
        plane = MemoryPlane(MemoryConfig(), tmp_path, devices=[])
        assert plane.boundary(1) == {}
        plane.close()
        assert not (tmp_path / "memory_summary.json").exists()
        assert plane.dump_oom(1, RuntimeError("RESOURCE_EXHAUSTED")) is None


# ---------------------------------------------------------------------------
# tree bytes (exact host-side accounting)
# ---------------------------------------------------------------------------


class TestTreeBytes:
    def test_sharded_tree_accounting(self, cpu_mesh):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        full = jax.device_put(
            jnp.zeros((8, 4), jnp.float32),
            NamedSharding(cpu_mesh, P(("data", "expert"))))
        repl = jax.device_put(jnp.zeros((4,), jnp.float32),
                              NamedSharding(cpu_mesh, P()))
        out = tree_bytes_by_subsystem(
            {"w": full}, {"mu": {"w": full}, "nu": {"w": full},
                          "master": {"w": repl}})
        # sharded [8,4] f32 over 4-way dp x 2-way tp... the ("data",
        # "expert") spec shards dim0 over data*expert=4; per-device shard
        # (2, 4) x 4B x 8 devices = 256B; replicated (4,) = 16B x 8 = 128B
        assert out["params"] == full.sharding.shard_shape((8, 4))[0] * 4 \
            * 4 * len(full.sharding.addressable_devices)
        assert out["opt_state"] == 2 * out["params"]
        assert out["master"] == 4 * 4 * 8

    def test_health_excluded_from_mu_nu(self):
        # opt_state = mu + nu + step; the health counters are forensic
        # bookkeeping, not optimizer state bytes worth calibrating against
        a = np.zeros((4,), np.float32)
        out = tree_bytes_by_subsystem(
            {"w": a}, {"mu": {"w": a}, "nu": {"w": a},
                       "health": {"c": np.zeros((), np.int32)},
                       "step": np.zeros((), np.int32)})
        assert out["opt_state"] == 16 + 16 + 4


# ---------------------------------------------------------------------------
# the compiled step's resident state, to the byte
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("zero1", [True, False], ids=["zero1", "no-zero1"])
@pytest.mark.parametrize("regime", ["mixed_precision", "bf16SR", "autocast",
                                    "fp32"])
def test_compiled_step_arguments_are_the_state_trees(devices8, regime, zero1,
                                                     tp):
    """What a device holds between steps, three ways that must agree to the
    byte: the compiled step's argument bytes (``memory_analysis()``: the
    state trees and a device's rows of the batch; the step's key is unused
    without dropout and pruned), ``tree_bytes_by_subsystem`` over the trees
    as the trainer lays them out, and the regime's own arithmetic: params in
    the param dtype, two float32 moments and, where the params are bfloat16,
    a float32 master copy, each of those over dp under ZeRO-1."""
    import math

    from jax.sharding import NamedSharding

    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        abstract_batch,
        abstract_opt_state,
        lower_step_program,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    asm = assemble_step_program(load_config({
        "name": "argbytes", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": 1},
        "exp_manager": {"create_tensorboard_logger": False,
                        "log_files": False},
        "distributed_strategy": {"tensor_model_parallel_size": tp,
                                 "zero1": zero1},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 16, "synthetic": True},
        "model": {"vocab_size": 64, "hidden_size": 32,
                  "intermediate_size": 64, "num_layers": 1,
                  "num_attention_heads": 4, "num_key_value_heads": 4,
                  "max_position_embeddings": 16,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": regime},
    }), build_data=False)
    mesh = asm.mesh
    n_dev, dp = mesh.devices.size, mesh.devices.size // tp

    def placed(tree, specs):
        return jax.tree_util.tree_map(
            lambda x, spec: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            tree, specs)

    params = placed(asm.abstract_params, asm.pspecs)
    held = tree_bytes_by_subsystem(
        params, placed(abstract_opt_state(asm), asm.ospecs))
    assert all(v % n_dev == 0 for v in held.values())
    held = {k: v // n_dev for k, v in held.items()}

    elements = sum(math.prod(x.sharding.shard_shape(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    over = dp if zero1 else 1
    assert elements % over == 0
    bf16_params = regime == "bf16SR"
    expected = {"params": elements * (2 if bf16_params else 4),
                "opt_state": 2 * 4 * elements // over + 4}  # + int32 step
    if bf16_params:
        expected["master"] = 4 * elements // over
    assert held == expected

    rows = {k: v.shape for k, v in abstract_batch(asm).items()}
    assert rows == {"input_ids": (8, 16), "labels": (8, 16)}
    batch_bytes = 2 * (8 // dp) * 16 * 4
    _, compiled = lower_step_program(asm)
    assert compiled.memory_analysis().argument_size_in_bytes \
        == sum(held.values()) + batch_bytes


# ---------------------------------------------------------------------------
# HBM calibration (autotune.cost_model)
# ---------------------------------------------------------------------------


def _synthetic_summary(**over):
    doc = {
        "schema": 1,
        "profile": {"total_bytes": 2000, "num_devices": 2,
                    "by_device": {"TPU_0": 1000, "TPU_1": 1000}},
        "attribution": {"activations": {"bytes": 600, "count": 3},
                        "chunk_store": {"bytes": 200, "count": 1}},
        "tree_bytes": {"params": 800, "opt_state": 400},
        "sampled": {"peak_hbm_bytes": 1200},
        "predicted": {"params": 500.0, "opt_state": 100.0,
                      "activations": 600.0, "pipeline_rings": 50.0,
                      "total": 1250.0},
    }
    doc.update(over)
    return doc


class TestHbmCalibration:
    def test_ratios_hand_computed(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            hbm_calibration_from_memory_summary,
        )

        cal = hbm_calibration_from_memory_summary(_synthetic_summary())
        # per-device measured: params 800/2=400 vs 500 -> 0.8;
        # opt_state 400/2=200 vs 100 -> 2.0; activations 600/2=300 vs
        # 600 -> 0.5; chunk_store 200/2=100 vs pipeline_rings 50 -> 2.0;
        # total: the sampled peak is ALREADY per-device (the worst single
        # device's watermark) — 1200 vs 1250 -> 0.96, NOT /n_dev
        assert cal["params"] == pytest.approx(0.8)
        assert cal["opt_state"] == pytest.approx(2.0)
        assert cal["activations"] == pytest.approx(0.5)
        assert cal["pipeline_rings"] == pytest.approx(2.0)
        assert cal["total"] == pytest.approx(0.96)

    def test_total_falls_back_to_profile_per_device(self):
        # without allocator stats the profile's all-device total divides
        # by the device count: 2000/2=1000 vs 1250 -> 0.8 — the same
        # per-device units the planner's prediction is in
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            hbm_calibration_from_memory_summary,
        )

        cal = hbm_calibration_from_memory_summary(
            _synthetic_summary(sampled={}))
        assert cal["total"] == pytest.approx(2000 / 2 / 1250)

    def test_no_predicted_raises(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            hbm_calibration_from_memory_summary,
        )

        with pytest.raises(ValueError, match="calibrat"):
            hbm_calibration_from_memory_summary(
                _synthetic_summary(predicted=None))

    def test_ratios_clamped(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            hbm_calibration_from_memory_summary,
        )

        doc = _synthetic_summary(
            tree_bytes={"params": 10**12}, predicted={"params": 1.0})
        cal = hbm_calibration_from_memory_summary(doc)
        assert cal["params"] == 20.0  # the sanity clamp

    def test_breakdown_applies_ratios(self):
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            hbm_breakdown,
        )
        from neuronx_distributed_training_tpu.autotune.space import (
            ModelFacts,
        )
        from neuronx_distributed_training_tpu.config.loader import (
            load_config,
        )

        cfg = load_config(_plan_raw_cfg())
        facts = ModelFacts.from_config(cfg)
        plan = facts.declared_plan_for(2)
        base = hbm_breakdown(facts, plan)
        cal = hbm_breakdown(facts, plan, calibration={"params": 2.0})
        assert cal["params"] == pytest.approx(2.0 * base["params"])
        assert cal["total"] == pytest.approx(
            base["total"] + base["params"])

    def test_priced_calibration_is_conservative(self):
        """Transient-category ratios floor at 1.0 in pricing (a boundary
        capture can't see freed step transients), state ratios move both
        ways, and the audit-only ``total`` is dropped."""
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            priced_hbm_calibration,
        )

        priced = priced_hbm_calibration(
            {"params": 0.8, "opt_state": 2.0, "activations": 0.05,
             "pipeline_rings": 1.7, "total": 0.3})
        assert priced == {"params": 0.8, "opt_state": 2.0,
                          "activations": 1.0, "pipeline_rings": 1.7}

    def test_load_memory_summary_from_dir(self, tmp_path):
        doc = _synthetic_summary()
        (tmp_path / "memory_summary.json").write_text(json.dumps(doc))
        assert load_memory_summary(tmp_path)["sampled"] == doc["sampled"]


# ---------------------------------------------------------------------------
# live fit() integration
# ---------------------------------------------------------------------------


def _fit_cfg(tmp_path, *, memory=None, max_steps=5, extra_tel=None,
             extra_em=None):
    from neuronx_distributed_training_tpu.config.loader import load_config

    tel = {"memory": memory if memory is not None
           else {"enabled": True, "start_step": 1, "num_steps": 2}}
    tel.update(extra_tel or {})
    em = {"exp_dir": str(tmp_path / "exp"),
          "create_tensorboard_logger": False, "log_files": False,
          "telemetry": tel}
    em.update(extra_em or {})
    return load_config({
        "name": "memsmoke", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": max_steps, "log_every_n_steps": 1},
        "exp_manager": em,
        "distributed_strategy": {"tensor_model_parallel_size": 2,
                                 "sequence_parallel": True},
        "data": {"global_batch_size": 8, "micro_batch_size": 2,
                 "seq_length": 32, "synthetic": True},
        "model": {"vocab_size": 128, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    })


class TestLiveFit:
    def test_memory_summary_from_real_fit(self, tmp_path, devices8):
        """The acceptance bar: a live CPU tiny-llama fit() produces a
        memory_summary.json whose attribution total reconciles with the
        profile's in-use bytes, with tree bytes + the planner's predicted
        breakdown stamped alongside."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        # the profile holds every live buffer of the process, and a family
        # file that ran on this worker keeps its toy's weights, which an
        # ``init_params`` made too (tests/family_ladder.py::Programs): what
        # the classes hold before this fit is not this fit's
        gc.collect()
        kept = attribute_profile(parse_memory_profile(
            jax.profiler.device_memory_profile()))
        t = Trainer.from_config(_fit_cfg(tmp_path),
                                enable_checkpointing=False)
        t.fit()
        path = Path(t.exp.log_dir) / "memory_summary.json"
        assert path.exists()
        s = json.loads(path.read_text())
        total = s["profile"]["total_bytes"]
        assert total > 0
        att = s["attribution"]
        assert sum(r["bytes"] for r in att.values()) == total
        assert "unattributed" in att or all(
            cls in ("params", "opt_state", "master", "ema", "activations",
                    "chunk_store", "moe_workspace", "batch", "executable")
            for cls in att)
        # the exact tree join: params + mu/nu carved out of the donated
        # dispatch pool by their true sizes
        tb = s["tree_bytes"]
        assert tb["params"] > 0 and tb["opt_state"] > 0
        for cls in ("params", "opt_state"):
            before = kept.get(cls, {"bytes": 0})["bytes"]
            assert att[cls]["bytes"] - before == tb[cls]
        # the planner's prediction rides along (predicted-vs-actual in one
        # artifact)
        assert s["predicted"] and s["predicted"]["total"] > 0
        # the run_summary mirror
        rs = json.loads(
            (Path(t.exp.log_dir) / "run_summary.json").read_text())
        assert rs["memory"]["in_use_bytes"] == total

    def test_planner_calibration_round_trip(self, tmp_path, devices8):
        """memory_summary.json from a live capture feeds plan_config:
        measured-vs-prior HBM ratios land in the PlanReport (format + dict)
        and reprice the lattice."""
        from neuronx_distributed_training_tpu.autotune import plan_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(_fit_cfg(tmp_path),
                                enable_checkpointing=False)
        t.fit()
        path = Path(t.exp.log_dir) / "memory_summary.json"
        rep = plan_config(_plan_raw_cfg(), chips=2, audit=False,
                          calibration=str(path))
        assert rep.error is None
        assert rep.hbm_calibration
        assert "params" in rep.hbm_calibration
        assert "total" in rep.hbm_calibration
        assert "HBM calibration (measured/prior)" in rep.format()
        assert rep.to_dict()["hbm_calibration"]

    def test_oom_drill_through_fault_injector(self, tmp_path, devices8):
        """FaultInjector mode=oom at step 3: the RESOURCE_EXHAUSTED escapes
        fit(), and the complete oom_<step>/ bundle is on disk first —
        samples ring, attribution, census memory_analysis bytes, predicted
        breakdown."""
        from neuronx_distributed_training_tpu.trainer.elastic import (
            FaultInjector,
            SimulatedOOM,
        )
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(_fit_cfg(tmp_path, max_steps=8),
                                enable_checkpointing=False)
        t.fault_injector = FaultInjector(at_step=3, mode="oom")
        with pytest.raises(SimulatedOOM, match="RESOURCE_EXHAUSTED"):
            t.fit()
        bundles = sorted(Path(t.exp.log_dir).glob("oom_*"))
        assert len(bundles) == 1
        doc = json.loads((bundles[0] / "oom.json").read_text())
        assert doc["kind"] == "oom"
        assert "RESOURCE_EXHAUSTED" in doc["error"]
        assert doc["attribution_at_death"]
        assert doc["tree_bytes"] is None or doc["tree_bytes"]["params"] > 0
        assert doc["predicted_hbm_breakdown"]["total"] > 0
        # the compile census ran at step 0, so its memory_analysis bytes
        # are in the bundle (predicted-vs-actual in ONE artifact)
        assert doc["memory_analysis"] and doc["memory_analysis"]["peak_bytes"] > 0
        assert (bundles[0] / "samples.json").exists()
        rs = json.loads(
            (Path(t.exp.log_dir) / "run_summary.json").read_text())
        assert rs["oom"]["step"] == 3
        json.dumps(doc, allow_nan=False)  # strict JSON

    def test_aot_once_and_dispatch_ahead_with_memory(self, tmp_path,
                                                     devices8):
        """Memory observability must add ZERO host syncs between boundaries
        and keep the AOT-once contract — the instrumented-step proof the
        fleet/control layers pin, with the memory plane on."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _fit_cfg(tmp_path, max_steps=6,
                       extra_tel={"fleet": {"enabled": True}})
        cfg["trainer"]["log_every_n_steps"] = 3
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

    def test_aot_once_with_memory_enabled(self, tmp_path, devices8):
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(_fit_cfg(tmp_path, max_steps=5),
                                enable_checkpointing=False)
        t.fit()
        assert not hasattr(t.train_step, "lower")  # AOT-once held
        assert t.step == 5


def _plan_raw_cfg():
    """A plannable raw config matching the live-fit tiny llama (tp=2)."""
    return {
        "name": "memplan", "model_source": "hf",
        "trainer": {"max_steps": 1, "devices": 2},
        "distributed_strategy": {"tensor_model_parallel_size": 2,
                                 "zero1": True},
        "data": {"seq_length": 32, "global_batch_size": 8,
                 "micro_batch_size": 4, "synthetic": True},
        "model": {"architecture": "llama", "vocab_size": 128,
                  "hidden_size": 64, "intermediate_size": 128,
                  "num_layers": 2, "num_attention_heads": 4,
                  "num_key_value_heads": 2,
                  "max_position_embeddings": 32},
        "precision": {"type": "mixed_precision"},
    }


# ---------------------------------------------------------------------------
# report CLIs
# ---------------------------------------------------------------------------


class TestReportCLIs:
    def test_memory_report_on_fixture_json_contract(self):
        """The verify-SKILL smoke: memory_report on the committed pprof
        fixture must render the attribution table and end with a parseable
        JSON last line (the shared tools/_jsonout contract)."""
        out = subprocess.run(
            [sys.executable,
             str(Path(__file__).parent.parent / "tools" / "memory_report.py"),
             str(FIXTURE), "--json", "-"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "unattributed" in out.stdout
        payload = json.loads(out.stdout.strip().splitlines()[-1])
        assert payload["total_bytes"] == FIXTURE_TOTAL
        got = {cls: rec["bytes"] for cls, rec in payload["attribution"].items()
               if rec["bytes"]}
        assert got == FIXTURE_ATTRIBUTION_NO_HINTS

    def test_memory_report_on_summary_and_oom(self, tmp_path):
        doc = _synthetic_summary()
        p = tmp_path / "memory_summary.json"
        p.write_text(json.dumps(doc))
        out = subprocess.run(
            [sys.executable,
             str(Path(__file__).parent.parent / "tools" / "memory_report.py"),
             str(tmp_path), "--json", "-"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "predicted vs measured" in out.stdout
        assert json.loads(out.stdout.strip().splitlines()[-1])["schema"] == 1

    def test_metrics_report_renders_memory_section(self, tmp_path):
        run = tmp_path / "run"
        run.mkdir()
        (run / "run_summary.json").write_text(json.dumps({
            "memory": {"profiled_step": 2, "in_use_bytes": 1000,
                       "attribution": {"params": 600, "unattributed": 400}},
            "oom": {"step": 4, "bundle": "oom_00000004", "error": "boom"},
        }))
        (run / "metrics.jsonl").write_text(
            json.dumps({"step": 1, "loss": 1.0}) + "\n")
        out = subprocess.run(
            [sys.executable,
             str(Path(__file__).parent.parent / "tools"
                 / "metrics_report.py"), str(run)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "memory (telemetry.memory" in out.stdout
        assert "OOM at step 4" in out.stdout
        assert "params" in out.stdout


if __name__ == "__main__":
    if "--regen-fixture" in sys.argv:
        FIXTURE.parent.mkdir(parents=True, exist_ok=True)
        FIXTURE.write_bytes(build_fixture_bytes())
        print(f"wrote {FIXTURE} ({FIXTURE.stat().st_size} bytes)")
    else:
        raise SystemExit(pytest.main([__file__, "-q", *sys.argv[1:]]))
