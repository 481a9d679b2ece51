"""Numerics flight recorder (telemetry/health + flight_recorder + the
trainer/optimizer wiring): config validation, in-graph probes (grouped grad
norms sharing the clipping reduction, finiteness flags, skip_update's bitwise
no-op), the host-side ring buffer + anomaly bundles, per-policy fault
injection through a real tiny-llama fit(), the healthy-path overhead contract
(AOT once, zero retraces, zero extra host syncs between boundaries), the hang
watchdog, and the tools/anomaly_report.py renderer — all tier-1 / CPU."""

import importlib.util
import json
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_training_tpu.optim.adamw import (
    AdamWConfig,
    adamw_update,
    global_norm,
    grouped_sq_norms,
    init_opt_state,
    opt_state_specs,
)
from neuronx_distributed_training_tpu.telemetry import (
    HealthConfig,
    HealthMonitor,
    HangWatchdog,
    TelemetryConfig,
    grad_group_of,
)
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


class TestHealthConfig:
    def test_defaults_disabled(self):
        hc = TelemetryConfig.from_config(None).health
        assert hc.enabled is False
        assert hc.policy == "dump_and_continue"
        assert hc.ring_buffer_steps == 32
        assert hc.watchdog_timeout_seconds == 0.0

    def test_bare_bool_enables(self):
        assert HealthConfig.from_config(True).enabled is True
        assert HealthConfig.from_config(False).enabled is False

    def test_unknown_key_rejected_at_load(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = {"exp_manager": {"telemetry": {"health": {"polcy": "halt"}}},
               "data": {"global_batch_size": 8, "micro_batch_size": 1}}
        with pytest.raises(ValueError, match="polcy"):
            load_config(cfg)

    def test_bad_policy_rejected_at_load(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = {"exp_manager": {"telemetry": {"health": {"policy": "ignore"}}},
               "data": {"global_batch_size": 8, "micro_batch_size": 1}}
        with pytest.raises(ValueError, match="halt"):
            load_config(cfg)

    def test_bad_values_rejected(self):
        with pytest.raises(ValueError, match="ring_buffer_steps"):
            HealthConfig.from_config({"ring_buffer_steps": 0})
        with pytest.raises(ValueError, match="watchdog_timeout_seconds"):
            HealthConfig.from_config({"watchdog_timeout_seconds": -1})
        with pytest.raises(ValueError, match="boolean"):
            HealthConfig.from_config({"enabled": "yes"})
        with pytest.raises(ValueError, match="max_bundles"):
            HealthConfig.from_config({"max_bundles": 0})

    def test_watchdog_without_any_monitor_rejected(self):
        # a watchdog that silently never arms is worse than a loud config
        # error — the dump path needs a bundle-capable monitor, which any
        # of health / fleet / control / a dump-action alert rule arms (the
        # cross-block check lives in TelemetryConfig, which sees them all)
        with pytest.raises(ValueError, match="bundle-capable"):
            TelemetryConfig.from_config({"health": {
                "enabled": False, "watchdog_timeout_seconds": 300}})
        # ...and each bundle-capable block legalizes it
        for block in ({"health": {"enabled": True,
                                  "watchdog_timeout_seconds": 300}},
                      {"health": {"watchdog_timeout_seconds": 300},
                       "fleet": {"enabled": True}},
                      {"health": {"watchdog_timeout_seconds": 300},
                       "control": {"enabled": True}},
                      {"health": {"watchdog_timeout_seconds": 300},
                       "alerts": [{"metric": "loss", "threshold": 1.0,
                                   "action": "dump"}]}):
            t = TelemetryConfig.from_config(block)
            assert t.health.watchdog_timeout_seconds == 300.0

    def test_blanket_telemetry_off_keeps_health_disabled(self):
        assert TelemetryConfig.from_config(False).health.enabled is False
        # blanket True switches the bool knobs but never silently opts into
        # the opt-state-changing health subtree
        assert TelemetryConfig.from_config(True).health.enabled is False

    def test_round_trip_through_loader(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = load_config({
            "exp_manager": {"telemetry": {"health": {
                "enabled": True, "policy": "skip_update",
                "ring_buffer_steps": 4, "watchdog_timeout_seconds": 9.0}}},
            "data": {"global_batch_size": 8, "micro_batch_size": 1},
        })
        hc = TelemetryConfig.from_config(
            cfg["exp_manager"]["telemetry"]).health
        assert hc.enabled and hc.policy == "skip_update"
        assert hc.ring_buffer_steps == 4
        assert hc.watchdog_timeout_seconds == 9.0


# ---------------------------------------------------------------------------
# grad grouping + grouped norms == clipping norm (one source of truth)
# ---------------------------------------------------------------------------


def _params():
    k = jax.random.PRNGKey(0)
    return {
        "embed": {"embedding": jax.random.normal(k, (16, 8))},
        "layers": {
            "attn": {"qkv": {"w": jax.random.normal(k, (2, 8, 8))}},
            "mlp": {"down": {"w": jax.random.normal(k, (2, 8, 8))}},
            "input_norm": {"scale": jnp.ones((2, 8))},
        },
        "final_norm": {"scale": jnp.ones((8,))},
    }


class TestGradGroups:
    def test_group_names(self):
        grads = _params()
        groups = grouped_sq_norms(grads, grad_group_of)
        assert set(groups) == {"embed", "layers/attn", "layers/mlp",
                               "layers/input_norm", "final_norm"}

    def test_grouped_sums_reproduce_global_norm(self):
        grads = _params()
        groups = grouped_sq_norms(grads, grad_group_of)
        np.testing.assert_allclose(
            float(jnp.sqrt(sum(groups.values()))), float(global_norm(grads)),
            rtol=1e-6)

    def test_adamw_reports_groups_and_identical_gnorm(self):
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        opt = init_opt_state(params)
        _, _, plain = adamw_update(params, grads, opt, 1e-3, AdamWConfig())
        _, _, grouped = adamw_update(params, grads, opt, 1e-3, AdamWConfig(),
                                     grad_group_fn=grad_group_of)
        np.testing.assert_allclose(float(grouped["grad_norm"]),
                                   float(plain["grad_norm"]), rtol=1e-6)
        assert bool(grouped["updates_finite"])
        assert set(grouped["group_norms"]) == {
            "embed", "layers/attn", "layers/mlp", "layers/input_norm",
            "final_norm"}

    def test_grouped_update_matches_plain(self):
        # the health probes must not perturb the update itself
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        opt = init_opt_state(params)
        p1, s1, _ = adamw_update(params, grads, opt, 1e-3, AdamWConfig())
        p2, s2, _ = adamw_update(params, grads, opt, 1e-3, AdamWConfig(),
                                 grad_group_fn=grad_group_of)
        for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                        jax.tree_util.tree_leaves((p2, s2))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# skip_nonfinite: the in-graph update suppression
# ---------------------------------------------------------------------------


def _trees_bitwise_equal(a, b) -> bool:
    return bool(jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool(jnp.array_equal(x, y, equal_nan=True)), a, b)))


class TestSkipNonfinite:
    def test_nan_grads_freeze_everything(self):
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        grads["layers"]["attn"]["qkv"]["w"] = (
            grads["layers"]["attn"]["qkv"]["w"].at[0, 0, 0].set(jnp.nan))
        opt = init_opt_state(params)
        new_p, new_s, m = adamw_update(params, grads, opt, 1e-3, AdamWConfig(),
                                       skip_nonfinite=True)
        assert not bool(m["updates_finite"])
        assert _trees_bitwise_equal(new_p, params)
        assert _trees_bitwise_equal(new_s, opt)  # incl. the step counter

    def test_finite_grads_update_exactly_as_without_skip(self):
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        opt = init_opt_state(params)
        p1, s1, _ = adamw_update(params, grads, opt, 1e-3, AdamWConfig())
        p2, s2, m = adamw_update(params, grads, opt, 1e-3, AdamWConfig(),
                                 skip_nonfinite=True)
        assert bool(m["updates_finite"])
        assert _trees_bitwise_equal((p1, s1), (p2, s2))

    def test_extra_finite_flag_forces_skip(self):
        # a NaN loss with finite grads (e.g. an aux-path NaN) must still skip
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        opt = init_opt_state(params)
        new_p, new_s, m = adamw_update(
            params, grads, opt, 1e-3, AdamWConfig(),
            skip_nonfinite=True, extra_finite=jnp.asarray(False))
        assert not bool(m["updates_finite"])
        assert _trees_bitwise_equal(new_p, params)

    def test_inf_grads_also_skip(self):
        params = _params()
        grads = jax.tree_util.tree_map(lambda p: 0.1 * p, params)
        grads["embed"]["embedding"] = (
            grads["embed"]["embedding"].at[0, 0].set(jnp.inf))
        opt = init_opt_state(params)
        new_p, _, m = adamw_update(params, grads, opt, 1e-3, AdamWConfig(),
                                   skip_nonfinite=True)
        assert not bool(m["updates_finite"])
        assert _trees_bitwise_equal(new_p, params)


class TestHealthOptState:
    def test_init_and_specs_shapes_match(self, cpu_mesh):
        from jax.sharding import PartitionSpec as P

        params = _params()
        state = init_opt_state(params, health=True)
        assert set(state["health"]) == {
            "steps_seen", "nonfinite_count", "skipped_count",
            "last_nonfinite_step"}
        assert int(state["health"]["last_nonfinite_step"]) == -1
        pspecs = jax.tree_util.tree_map(lambda _: P(), params)
        ospecs = opt_state_specs(params, pspecs, cpu_mesh, health=True)
        # spec tree structure must match the state tree structure exactly
        assert (jax.tree_util.tree_structure(state)
                == jax.tree_util.tree_structure(
                    jax.tree_util.tree_map(
                        lambda x: x, ospecs,
                        is_leaf=lambda x: isinstance(x, P))))


# ---------------------------------------------------------------------------
# make_train_step: in-graph probes on a real tiny llama step
# ---------------------------------------------------------------------------


def _llama_step(policy_name="skip_update", param_norm=True):
    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.optim.lr import constant_lr
    from neuronx_distributed_training_tpu.trainer.step import make_train_step

    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_attention_heads=4, num_kv_heads=2, max_position_embeddings=16)
    policy = DtypePolicy()
    params = llama.init_params(jax.random.PRNGKey(0), cfg, policy)
    opt = init_opt_state(params, policy, health=True)
    hc = HealthConfig(enabled=True, policy=policy_name, param_norm=param_norm)

    def loss_fn(p, batch, key):
        return llama.forward(p, batch, cfg, policy)

    step = jax.jit(make_train_step(
        loss_fn, AdamWConfig(), constant_lr(1e-3), policy, health_cfg=hc))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64,
                             dtype=jnp.int32)
    clean = {"input_ids": ids, "labels": ids,
             "loss_mask": jnp.ones((4, 16), jnp.float32)}
    poisoned = dict(clean, loss_mask=jnp.full((4, 16), jnp.nan, jnp.float32))
    return step, params, opt, clean, poisoned


class TestTrainStepHealth:
    def test_healthy_step_metrics(self):
        step, params, opt, clean, _ = _llama_step()
        _, o1, m = step(params, opt, clean, jax.random.PRNGKey(2))
        assert float(m["health/updates_finite"]) == 1.0
        assert float(m["health/loss_finite"]) == 1.0
        assert float(m["health/nonfinite_count"]) == 0.0
        assert float(m["health/last_nonfinite_step"]) == -1.0
        assert m["health/param_norm"] > 0.0
        groups = {k for k in m if k.startswith("health/grad_norm/")}
        assert "health/grad_norm/layers/attn" in groups
        assert "health/grad_norm/embed" in groups
        assert int(o1["health"]["steps_seen"]) == 1

    def test_nan_batch_suppresses_update_bitwise(self):
        step, params, opt, clean, poisoned = _llama_step("skip_update")
        p1, o1, _ = step(params, opt, clean, jax.random.PRNGKey(2))
        p2, o2, m = step(p1, o1, poisoned, jax.random.PRNGKey(3))
        assert float(m["health/updates_finite"]) == 0.0
        assert float(m["health/skipped_count"]) == 1.0
        assert float(m["health/last_nonfinite_step"]) == 1.0
        assert _trees_bitwise_equal(p2, p1)
        # AdamW's own step counter froze; the invocation counter advanced
        assert int(o2["step"]) == int(o1["step"])
        assert int(o2["health"]["steps_seen"]) == 2
        # training resumes: the next clean step applies a normal update
        p3, o3, m3 = step(p2, o2, clean, jax.random.PRNGKey(4))
        assert float(m3["health/updates_finite"]) == 1.0
        assert float(m3["health/nonfinite_count"]) == 1.0
        assert np.isfinite(float(m3["loss"]))
        assert not _trees_bitwise_equal(p3, p2)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(p3))

    def test_dump_and_continue_counts_but_applies(self):
        # without skip_update the poisoned update flows through (and the
        # counters record it) — the documented dump_and_continue semantics
        step, params, opt, clean, poisoned = _llama_step("dump_and_continue")
        p1, o1, _ = step(params, opt, clean, jax.random.PRNGKey(2))
        p2, o2, m = step(p1, o1, poisoned, jax.random.PRNGKey(3))
        assert float(m["health/nonfinite_count"]) == 1.0
        assert float(m["health/skipped_count"]) == 0.0
        assert not _trees_bitwise_equal(p2, p1)  # the NaN update applied
        assert int(o2["step"]) == int(o1["step"]) + 1

    def test_param_norm_knob_off(self):
        step, params, opt, clean, _ = _llama_step(param_norm=False)
        _, _, m = step(params, opt, clean, jax.random.PRNGKey(2))
        assert "health/param_norm" not in m

    def test_disabled_health_adds_no_keys(self):
        from neuronx_distributed_training_tpu.models import llama
        from neuronx_distributed_training_tpu.optim.lr import constant_lr
        from neuronx_distributed_training_tpu.trainer.step import (
            make_train_step,
        )

        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
            num_attention_heads=4, num_kv_heads=2, max_position_embeddings=16)
        policy = DtypePolicy()
        params = llama.init_params(jax.random.PRNGKey(0), cfg, policy)
        opt = init_opt_state(params, policy)

        def loss_fn(p, batch, key):
            return llama.forward(p, batch, cfg, policy)

        step = make_train_step(loss_fn, AdamWConfig(), constant_lr(1e-3),
                               policy, health_cfg=HealthConfig(enabled=False))
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64,
                                 dtype=jnp.int32)
        _, o, m = jax.jit(step)(params, opt,
                                {"input_ids": ids, "labels": ids},
                                jax.random.PRNGKey(2))
        assert not any(k.startswith("health/") for k in m)
        assert "health" not in o


# ---------------------------------------------------------------------------
# HealthMonitor: ring buffer + bundles
# ---------------------------------------------------------------------------


def _mon(tmp_path, **kw):
    defaults = dict(enabled=True, policy="dump_and_continue",
                    ring_buffer_steps=4)
    defaults.update(kw)
    return HealthMonitor(HealthConfig(**defaults), dump_dir=tmp_path,
                         run_facts={"model_family": "LlamaConfig"})


class TestHealthMonitor:
    def _feed(self, mon, steps, bad_at=()):
        count = 0
        for s in range(steps):
            if s in bad_at:
                count += 1
            mon.record(s, {"loss": float(s), "health/nonfinite_count": count},
                       fingerprint={"arg0['x']": "f32[8]"},
                       spans={"dispatch": 0.1 * s})
        return count

    def test_healthy_boundary_is_noop(self, tmp_path):
        mon = _mon(tmp_path)
        self._feed(mon, 5)
        assert mon.check_boundary(5, {"health/nonfinite_count": 0.0}) is None
        assert not list(Path(tmp_path).glob("anomaly_*"))

    def test_missing_counter_is_noop(self, tmp_path):
        mon = _mon(tmp_path)
        assert mon.check_boundary(5, {"loss": 1.0}) is None

    def test_anomaly_dumps_bundle_once(self, tmp_path):
        mon = _mon(tmp_path)
        self._feed(mon, 4, bad_at={2})
        fetched = {"health/nonfinite_count": 1.0,
                   "health/last_nonfinite_step": 2.0, "loss": float("nan")}
        assert mon.check_boundary(4, fetched) == "dump_and_continue"
        # same counter at the next boundary: no new bundle, no action
        assert mon.check_boundary(5, fetched) is None
        bundles = sorted(Path(tmp_path).glob("anomaly_*"))
        assert len(bundles) == 1
        summary = json.loads((bundles[0] / "anomaly.json").read_text())
        assert summary["anomaly_step"] == 2
        assert summary["trigger_step"] == 4
        assert summary["rng"] == {"seed": 0, "fold_in": 2}
        assert "run_summary.json" in summary["compile_census"]
        assert summary["run_facts"]["model_family"] == "LlamaConfig"

    def test_ring_holds_min_k_n_prior_steps(self, tmp_path):
        # anomaly at step k with depth N: ring must hold >= min(k, N) priors
        for k, n in ((2, 8), (6, 4)):
            mon = _mon(tmp_path / f"k{k}", ring_buffer_steps=n)
            self._feed(mon, k + 1, bad_at={k})
            mon.check_boundary(k + 1, {"health/nonfinite_count": 1.0,
                                       "health/last_nonfinite_step": float(k)})
            bundle = next((Path(tmp_path) / f"k{k}").glob("anomaly_*"))
            ring = json.loads((bundle / "ring.json").read_text())
            prior = [e for e in ring if e["step"] < k]
            assert len(prior) >= min(k, n - 1), (k, n, [e["step"] for e in ring])
            assert ring[-1]["step"] == k
            # forensic fields present per entry
            assert ring[-1]["fingerprint"] == {"arg0['x']": "f32[8]"}
            assert ring[-1]["rng"] == {"seed": 0, "fold_in": k}
            assert "spans_cumulative" in ring[-1]

    def test_max_bundles_cap(self, tmp_path):
        mon = _mon(tmp_path, max_bundles=2)
        for step in (1, 2, 3):
            mon.record(step, {"health/nonfinite_count": step})
            mon.check_boundary(step + 1,
                               {"health/nonfinite_count": float(step),
                                "health/last_nonfinite_step": float(step)})
        assert len(list(Path(tmp_path).glob("anomaly_*"))) == 2

    def test_multiple_bad_steps_in_one_window_each_get_bundles(self, tmp_path):
        # counter jumps by 2 inside one logging window: BOTH still-buffered
        # bad steps must get their own bundle, not just last_nonfinite_step
        mon = _mon(tmp_path, ring_buffer_steps=8)
        for s in range(6):
            bad = s in (3, 5)
            mon.record(s, {"health/updates_finite": 0.0 if bad else 1.0,
                           "health/nonfinite_count": float(sum(
                               x <= s for x in (3, 5)))})
        assert mon.check_boundary(
            6, {"health/nonfinite_count": 2.0,
                "health/last_nonfinite_step": 5.0}) == "dump_and_continue"
        assert sorted(b.name for b in Path(tmp_path).glob("anomaly_*")) == [
            "anomaly_00000003", "anomaly_00000005"]

    def test_seed_counters_suppresses_resume_retrigger(self, tmp_path):
        # a fresh monitor (restart) must not re-trigger on a counter that a
        # previous incarnation already handled
        mon = _mon(tmp_path)
        mon.seed_counters(2)
        assert mon.check_boundary(500, {"health/nonfinite_count": 2.0}) is None
        assert not list(Path(tmp_path).glob("anomaly_*"))

    def test_resume_extends_prior_anomaly_trail(self, tmp_path):
        # run_summary.json's anomaly list survives a restart: the new
        # monitor seeds from it and appends instead of overwriting
        import json as _json

        prior = [{"step": 100, "bundle": "anomaly_00000100",
                  "policy": "skip_update"}]
        (tmp_path / "run_summary.json").write_text(
            _json.dumps({"anomalies": prior}))
        written = {}
        mon = HealthMonitor(
            HealthConfig(enabled=True, ring_buffer_steps=4),
            dump_dir=tmp_path, write_run_summary=written.update)
        mon.record(900, {"health/nonfinite_count": 1})
        mon.check_boundary(901, {"health/nonfinite_count": 1.0,
                                 "health/last_nonfinite_step": 900.0})
        assert [a["step"] for a in written["anomalies"]] == [100, 900]

    def test_failed_write_burns_neither_dedupe_nor_budget(self, tmp_path,
                                                          monkeypatch):
        import neuronx_distributed_training_tpu.telemetry.flight_recorder as fr

        mon = _mon(tmp_path, max_bundles=1)
        calls = {"n": 0}
        orig = fr.json.dump

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("No space left on device")
            return orig(*a, **kw)

        monkeypatch.setattr(fr.json, "dump", flaky)
        assert mon.dump(3) is None  # transient ENOSPC
        bundle = mon.dump(3)  # retry: dedupe slot and cap were not consumed
        assert bundle is not None and (bundle / "anomaly.json").exists()

    def test_malformed_prior_trail_entry_skipped_not_fatal(self, tmp_path):
        (tmp_path / "run_summary.json").write_text(json.dumps({"anomalies": [
            {"step": 1, "bundle": "anomaly_00000001", "policy": "p"},
            {"bundle": "anomaly_nostep"},  # malformed: no step
            {"step": 3, "bundle": "anomaly_00000003", "policy": "p"}]}))
        mon = _mon(tmp_path)
        # one bad entry must not drop the rest of the prior trail
        assert [a["step"] for a in mon.anomalies] == [1, 3]

    def test_write_failed_anomaly_retries_at_next_boundary(self, tmp_path,
                                                           monkeypatch):
        import neuronx_distributed_training_tpu.telemetry.flight_recorder as fr

        mon = _mon(tmp_path)
        mon.record(2, {"health/nonfinite_count": 1})
        calls = {"n": 0}
        orig = fr.json.dump

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("No space left on device")
            return orig(*a, **kw)

        monkeypatch.setattr(fr.json, "dump", flaky)
        fetched = {"health/nonfinite_count": 1.0,
                   "health/last_nonfinite_step": 2.0}
        # first boundary: write fails; the comparator must roll back so the
        # SAME counter value re-triggers at the next boundary
        assert mon.check_boundary(3, fetched) == "dump_and_continue"
        assert not list(Path(tmp_path).glob("anomaly_*"))
        assert mon.check_boundary(4, fetched) == "dump_and_continue"
        assert len(list(Path(tmp_path).glob("anomaly_*"))) == 1
        # and once dumped, the counter no longer triggers
        assert mon.check_boundary(5, fetched) is None

    def test_hang_dump_bypasses_anomaly_cap(self, tmp_path):
        mon = _mon(tmp_path, max_bundles=1)
        mon.record(1, {"health/nonfinite_count": 1})
        mon.check_boundary(2, {"health/nonfinite_count": 1.0,
                               "health/last_nonfinite_step": 1.0})
        # anomaly budget exhausted; the hang's stacks must still land
        bundle = mon.dump_hang(5, "host_sync", "stack text")
        assert bundle is not None and (bundle / "stacks.txt").exists()

    def test_run_summary_callback(self, tmp_path):
        written = {}
        mon = HealthMonitor(
            HealthConfig(enabled=True, ring_buffer_steps=4),
            dump_dir=tmp_path, write_run_summary=written.update)
        mon.record(0, {"health/nonfinite_count": 1})
        mon.check_boundary(1, {"health/nonfinite_count": 1.0,
                               "health/last_nonfinite_step": 0.0})
        assert written["anomalies"][0]["step"] == 0
        assert written["anomalies"][0]["bundle"].startswith("anomaly_")


# ---------------------------------------------------------------------------
# hang watchdog
# ---------------------------------------------------------------------------


class TestHangWatchdog:
    def test_fast_block_does_not_fire(self, tmp_path):
        mon = _mon(tmp_path)
        wd = HangWatchdog(5.0, mon, abort=False)
        with wd.guard("host_sync", 3):
            pass
        time.sleep(0.05)
        assert wd.fired is False
        assert not list(Path(tmp_path).glob("hang_*"))

    def test_hang_dumps_stacks_without_device_fetch(self, tmp_path):
        mon = _mon(tmp_path)
        mon.record(7, {"loss": jnp.asarray(1.0),
                       "health/nonfinite_count": jnp.asarray(0)},
                   fingerprint={"arg0['x']": "f32[8]"})
        wd = HangWatchdog(0.05, mon, abort=False)
        with wd.guard("host_sync", 7):
            time.sleep(0.4)
        assert wd.fired is True
        bundle = next(Path(tmp_path).glob("hang_*"))
        assert (bundle / "stacks.txt").exists()
        stacks = (bundle / "stacks.txt").read_text()
        assert "thread" in stacks
        summary = json.loads((bundle / "anomaly.json").read_text())
        assert summary["kind"] == "hang"
        assert summary["hung_operation"] == "host_sync"
        ring = json.loads((bundle / "ring.json").read_text())
        # device arrays must NOT have been fetched (hung backend): metric
        # values are replaced by their key list
        assert ring[-1]["metrics"] == {"keys": ["health/nonfinite_count",
                                                "loss"]}

    def test_fires_at_most_once_per_process(self, tmp_path):
        # under abort=False a chronically slow boundary must not write a
        # hang bundle per boundary (hang bundles bypass max_bundles on the
        # strength of this guarantee)
        mon = _mon(tmp_path)
        wd = HangWatchdog(0.05, mon, abort=False)
        with wd.guard("host_sync", 1):
            time.sleep(0.3)
        with wd.guard("host_sync", 2):
            time.sleep(0.3)
        assert wd.fired is True
        assert len(list(Path(tmp_path).glob("hang_*"))) == 1


# ---------------------------------------------------------------------------
# trainer integration: fault injection per policy through a real fit()
# ---------------------------------------------------------------------------


def _tiny_cfg(tmp_path, *, policy, max_steps=6, ring=8, log_every=1):
    from neuronx_distributed_training_tpu.config.loader import load_config

    return load_config({
        "name": "health", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": max_steps, "log_every_n_steps": log_every},
        "exp_manager": {"exp_dir": str(tmp_path / "exp"),
                        "create_tensorboard_logger": False,
                        "log_files": False,
                        "telemetry": {"health": {
                            "enabled": True, "policy": policy,
                            "ring_buffer_steps": ring}}},
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


def _nan_data_module(nan_steps, seed=3):
    from neuronx_distributed_training_tpu.data import SyntheticDataModule

    class NaNInjecting(SyntheticDataModule):
        """Synthetic LM batches with a NaN loss_mask at chosen step indices.

        The mask rides EVERY batch (all-ones normally) so the abstract batch
        signature never changes — the injection is a pure value fault, not a
        retrace."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._yielded = 0

        def global_batches(self):
            for b in super().global_batches():
                mask = np.ones_like(b["input_ids"], np.float32)
                if self._yielded in nan_steps:
                    mask[:] = np.nan
                self._yielded += 1
                yield dict(b, loss_mask=mask)

    return NaNInjecting(vocab_size=128, seq_len=32, global_batch_size=8,
                        seed=seed)


def _run(tmp_path, policy, nan_steps=frozenset({2}), **cfg_kw):
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = _tiny_cfg(tmp_path, policy=policy, **cfg_kw)
    t = Trainer.from_config(cfg, data_module=_nan_data_module(nan_steps),
                            enable_checkpointing=False)
    metrics = t.fit()
    return t, metrics, Path(t.exp.log_dir)


class TestFaultInjectionPolicies:
    def test_skip_update_suppresses_and_resumes(self, tmp_path, devices8):
        k = 2
        t, m, log_dir = _run(tmp_path, "skip_update", {k})
        assert t.step == 6  # training resumed to completion
        assert m["health/nonfinite_count"] == 1.0
        assert m["health/skipped_count"] == 1.0
        assert m["health/last_nonfinite_step"] == float(k)
        assert np.isfinite(m["loss"])
        # the skipped update left the params clean: every leaf finite
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(t.params))
        bundles = sorted(log_dir.glob("anomaly_*"))
        assert len(bundles) == 1  # exactly one bundle for the one bad step
        ring = json.loads((bundles[0] / "ring.json").read_text())
        assert len([e for e in ring if e["step"] < k]) >= min(k, 8)
        # bundles must be STRICT JSON: the bad step's nan loss/grad_norm are
        # serialized as strings, never bare NaN tokens
        for f in ("ring.json", "anomaly.json"):
            json.dumps(json.loads((bundles[0] / f).read_text()),
                       allow_nan=False)
        bad_entry = next(e for e in ring if e["step"] == k)
        assert bad_entry["metrics"]["loss"] == "nan"
        summary = json.loads((log_dir / "run_summary.json").read_text())
        assert summary["anomalies"] == [{"step": k,
                                         "bundle": bundles[0].name,
                                         "policy": "skip_update"}]

    def test_dump_and_continue_keeps_training(self, tmp_path, devices8):
        t, m, log_dir = _run(tmp_path, "dump_and_continue", {2})
        assert t.step == 6  # training ran to completion
        # documented semantics: the poisoned update APPLIED, so params are
        # NaN from step 2 on and every later step is non-finite too (2..5);
        # each newly-bad step gets its own bundle (deduped per step, capped
        # at max_bundles) — this cascade is exactly why skip_update exists
        assert m["health/nonfinite_count"] == 4.0
        assert m["health/skipped_count"] == 0.0
        bundles = sorted(log_dir.glob("anomaly_*"))
        assert [b.name for b in bundles] == [
            f"anomaly_{s:08d}" for s in (2, 3, 4, 5)]

    def test_halt_stops_without_checkpoint(self, tmp_path, devices8):
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        class FakeCheckpointer:
            """Records save() calls; stands in for orbax (absent on this
            image) so the halt-never-checkpoints contract is pinned."""

            class config:
                every_n_train_steps = 5

            def __init__(self):
                self.saved_steps = []

            def latest_step(self):
                return None

            def save(self, state, metrics=None):
                self.saved_steps.append(int(state.step))
                return True

            def wait(self):
                pass

            def close(self):
                pass

        cfg = _tiny_cfg(tmp_path, policy="halt")
        t = Trainer.from_config(cfg, data_module=_nan_data_module({2}),
                                enable_checkpointing=False)
        t.checkpointer = FakeCheckpointer()
        t.fit()
        # with log_every=1 the anomaly at step 2 is detected at boundary 3
        assert t.step == 3
        log_dir = Path(t.exp.log_dir)
        assert len(list(log_dir.glob("anomaly_*"))) == 1
        # halt must NOT checkpoint the poisoned state — neither the
        # stop-path save nor the final save may run
        assert t.checkpointer.saved_steps == []

    def test_resume_from_pre_health_checkpoint(self, tmp_path, devices8):
        """Flipping telemetry.health on must not strand an existing run: a
        checkpoint written WITHOUT the health subtree restores with fresh
        counters instead of crashing on the tree mismatch."""
        from neuronx_distributed_training_tpu.checkpoint import TrainState
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _tiny_cfg(tmp_path, policy="skip_update")
        t = Trainer.from_config(cfg, data_module=_nan_data_module(frozenset()),
                                enable_checkpointing=False)
        legacy_opt = {k: v for k, v in t.opt_state.items() if k != "health"}

        class LegacyCheckpointer:
            """Restores a pre-health checkpoint: raises on a template that
            carries the health subtree (the orbax structure-mismatch), like
            a real store would."""

            config = type("C", (), {"every_n_train_steps": 0})

            def latest_step(self):
                return 4

            def restore(self, params, opt_state, **kw):
                if "health" in opt_state:
                    raise ValueError("tree structure mismatch: 'health'")
                return TrainState(params=params, opt_state=opt_state,
                                  step=4, consumed_samples=32)

            def wait(self):
                pass

            def close(self):
                pass

        t.checkpointer = LegacyCheckpointer()
        assert t.maybe_resume() is True
        assert t.step == 4
        assert "health" in t.opt_state  # fresh counters re-attached
        assert int(t.opt_state["health"]["nonfinite_count"]) == 0
        # steps_seen realigned with the restored trainer step: future
        # last_nonfinite_step values (steps_seen - 1 at the bad step) must
        # name real trainer steps, not a counter restarted at 0
        assert int(t.opt_state["health"]["steps_seen"]) == 4
        assert set(t.opt_state) == set(legacy_opt) | {"health"}

    def test_census_write_failure_keeps_compiled_step(self, tmp_path,
                                                      devices8, monkeypatch):
        """A run_summary.json write error must not discard the finished
        executable and force a second compile."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _tiny_cfg(tmp_path, policy="skip_update", max_steps=2)
        t = Trainer.from_config(cfg, data_module=_nan_data_module(frozenset()),
                                enable_checkpointing=False)
        monkeypatch.setattr(
            t.exp, "write_run_summary",
            lambda *_a, **_k: (_ for _ in ()).throw(OSError("disk full")))
        t.fit()
        # the loop still swapped in (and ran) the AOT executable
        assert not hasattr(t.train_step, "lower")

    def test_detection_latency_is_log_interval(self, tmp_path, devices8):
        # log_every=3, anomaly at step 2 -> detected at boundary step 3;
        # skip_update protected the params in-graph at zero latency either way
        t, m, log_dir = _run(tmp_path, "skip_update", {2}, log_every=3)
        assert t.step == 6
        bundles = sorted(log_dir.glob("anomaly_*"))
        assert len(bundles) == 1
        assert json.loads(
            (bundles[0] / "anomaly.json").read_text())["trigger_step"] == 3


# ---------------------------------------------------------------------------
# healthy-path overhead contract: AOT once, zero retraces, health in sinks
# ---------------------------------------------------------------------------


class TestHealthyPathOverhead:
    @pytest.fixture(scope="class")
    def healthy_run(self, tmp_path_factory, devices8):
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        tmp_path = tmp_path_factory.mktemp("healthy")
        cfg = _tiny_cfg(tmp_path, policy="skip_update")
        t = Trainer.from_config(cfg, data_module=_nan_data_module(frozenset()),
                                enable_checkpointing=False)
        metrics = t.fit()
        return t, metrics, Path(t.exp.log_dir)

    def test_aot_executable_swapped_in(self, healthy_run):
        # the census AOT-compiles ONCE and the loop runs that executable:
        # health riding the same jit means no second compile ever happened
        t, _, _ = healthy_run
        assert not hasattr(t.train_step, "lower")

    def test_zero_retraces(self, healthy_run):
        t, _, log_dir = healthy_run
        summary = json.loads((log_dir / "run_summary.json").read_text())
        assert "retrace_events" not in summary
        assert "anomalies" not in summary

    def test_health_metrics_flow_through_sinks(self, healthy_run):
        _, _, log_dir = healthy_run
        records = [json.loads(l) for l in
                   (log_dir / "metrics.jsonl").read_text().splitlines()]
        last = records[-1]
        assert last["health/updates_finite"] == 1.0
        assert last["health/nonfinite_count"] == 0.0
        assert any(k.startswith("health/grad_norm/") for k in last)
        # and the census/goodput schema of PR 2 is intact alongside
        summary = json.loads((log_dir / "run_summary.json").read_text())
        assert summary["compile_seconds"] > 0
        assert "collectives" in summary

    def test_no_bundles_written(self, healthy_run):
        _, _, log_dir = healthy_run
        assert not list(log_dir.glob("anomaly_*"))
        assert not list(log_dir.glob("hang_*"))


class TestDispatchAheadContractWithHealth:
    def test_no_host_sync_between_boundaries(self, tmp_path, devices8):
        """Health must add ZERO host syncs between logging boundaries: with
        an instrumented step emitting health metrics, values are converted
        to host floats only at boundary steps (the monitor ring-buffers
        device references without touching them)."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = _tiny_cfg(tmp_path, policy="skip_update", max_steps=6,
                        log_every=3)
        t = Trainer.from_config(cfg, data_module=_nan_data_module(frozenset()),
                                enable_checkpointing=False)

        conversions: list[int] = []

        class _Scalar:
            def __init__(self, step, value=1.0):
                self.step, self.value = step, value

            def __float__(self):
                conversions.append(self.step)
                return self.value

        real_params, real_opt = t.params, t.opt_state

        def fake_step(params, opt_state, batch, key):
            return real_params, real_opt, {
                "loss": _Scalar(t.step),
                "grad_norm": _Scalar(t.step),
                "health/updates_finite": _Scalar(t.step),
                "health/nonfinite_count": _Scalar(t.step, 0.0),
                "health/last_nonfinite_step": _Scalar(t.step, -1.0),
            }

        t.train_step = fake_step
        t.fit()
        assert conversions, "boundaries must fetch metrics"
        # pre-increment step ids 2 and 5 -> boundaries at steps 3 and 6; the
        # ring-buffered steps 0,1,3,4 must never have been fetched
        assert set(conversions) == {2, 5}, sorted(set(conversions))


# ---------------------------------------------------------------------------
# tools/anomaly_report.py smoke
# ---------------------------------------------------------------------------


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestAnomalyReport:
    def _bundle(self, tmp_path):
        mon = _mon(tmp_path)
        for s in range(3):
            mon.record(s, {
                "loss": 4.0 - s if s < 2 else float("nan"),
                "grad_norm": 1.0 if s < 2 else float("nan"),
                "health/updates_finite": 1.0 if s < 2 else 0.0,
                "health/param_norm": 10.0 + 0.5 * s,
                "health/nonfinite_count": 0.0 if s < 2 else 1.0,
                "health/grad_norm/layers/attn": 0.5,
            }, fingerprint={"arg0['input_ids']": "int32[8,32]"})
        mon.check_boundary(3, {"health/nonfinite_count": 1.0,
                               "health/last_nonfinite_step": 2.0})
        return tmp_path

    def test_renders_bundle_dir_and_run_dir(self, tmp_path, capsys):
        ar = _load_tool("anomaly_report")
        run_dir = self._bundle(tmp_path)
        assert ar.main([str(run_dir)]) == 0  # run dir: newest bundle picked
        out = capsys.readouterr().out
        for needle in ("anomaly bundle — step 2", "dump_and_continue",
                       "fold_in(PRNGKey(0), 2)", "ring buffer", "layers/attn",
                       "pnorm_drift", "int32[8,32]"):
            assert needle in out, (needle, out)
        bundle = next(run_dir.glob("anomaly_*"))
        assert ar.main([str(bundle)]) == 0  # direct bundle path too

    def test_missing_bundle_errors(self, tmp_path):
        ar = _load_tool("anomaly_report")
        assert ar.main([str(tmp_path)]) == 2

    def test_newest_bundle_picked_by_step_not_name(self, tmp_path):
        # lexicographic order would rank hang_* above every anomaly_*
        ar = _load_tool("anomaly_report")
        for name, step in (("hang_00000010", 10), ("anomaly_00000500", 500)):
            d = tmp_path / name
            d.mkdir()
            (d / "anomaly.json").write_text(json.dumps(
                {"kind": name.split("_")[0], "anomaly_step": step}))
        assert ar.find_bundle(str(tmp_path)).endswith("anomaly_00000500")

    def test_renders_real_trainer_bundle(self, tmp_path, devices8, capsys):
        # the renderer must accept exactly what a real anomalous fit() writes
        ar = _load_tool("anomaly_report")
        _, _, log_dir = _run(tmp_path, "skip_update", {1}, max_steps=3)
        assert ar.main([str(log_dir)]) == 0
        out = capsys.readouterr().out
        assert "anomaly bundle — step 1" in out
        assert "per-group grad norms" in out

    def test_metrics_report_lists_anomalies(self, tmp_path, devices8, capsys):
        mr = _load_tool("metrics_report")
        _, _, log_dir = _run(tmp_path, "skip_update", {1}, max_steps=3)
        assert mr.main([str(log_dir)]) == 0
        out = capsys.readouterr().out
        assert "anomalies (1 forensic bundle" in out
        assert "anomaly_00000001" in out

    def test_metrics_report_tolerates_malformed_trail(self, tmp_path, capsys):
        mr = _load_tool("metrics_report")
        (tmp_path / "run_summary.json").write_text(json.dumps({
            "anomalies": [{"step": 2, "bundle": "anomaly_00000002",
                           "policy": "halt"},
                          "not-a-dict", {"bundle": "anomaly_nostep"}]}))
        assert mr.main([str(tmp_path / "run_summary.json")]) == 0
        out = capsys.readouterr().out
        assert "anomaly_00000002" in out
        assert "unreadable entry" in out
