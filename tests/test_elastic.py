"""Elastic resume: world-size-agnostic checkpoints, save retry/backoff,
restart-time replanning, the SIGTERM grace window, and the preemption drill.

The headline acceptance test (``TestDrill.test_kill_and_resume_at_smaller_dp``)
is the automated form of the fleet story: a tiny-llama run killed at step k
resumes on a DIFFERENT dp degree, the autotune replanner re-meshes it, and the
loss trajectory matches an uninterrupted control run at pinned tolerance with
the restart cost visible in goodput accounting (docs/elasticity.md).
"""

import errno
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from neuronx_distributed_training_tpu.checkpoint import (
    CheckpointConfig,
    Checkpointer,
    TrainState,
    is_transient_save_error,
)
from neuronx_distributed_training_tpu.config.loader import (
    batch_schedule,
    load_config,
)
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.trainer.elastic import (
    ElasticConfig,
    ElasticResumeError,
    FaultInjector,
    SimulatedPreemption,
    build_manifest,
    discover_checkpoint_dir,
    maybe_replan,
    plan_layout_reason,
    read_latest_manifest,
)

from elastic_drill import read_losses, run_drill, tiny_llama_config


# ---------------------------------------------------------------------------
# knob block
# ---------------------------------------------------------------------------


class TestElasticConfig:
    def test_defaults(self):
        ec = ElasticConfig.from_config(None)
        assert not ec.enabled
        assert ec.grace_period_seconds == 30.0
        assert ec.save_retries == 3

    def test_bare_bool_toggles_enabled(self):
        assert ElasticConfig.from_config(True).enabled
        assert not ElasticConfig.from_config(False).enabled

    def test_unknown_key_has_did_you_mean(self):
        with pytest.raises(ValueError, match="grace_period_seconds"):
            ElasticConfig.from_config({"grace_perid_seconds": 5})

    def test_ill_typed_and_negative_rejected(self):
        with pytest.raises(ValueError, match="boolean"):
            ElasticConfig.from_config({"enabled": "yes"})
        with pytest.raises(ValueError, match=">= 0"):
            ElasticConfig.from_config({"save_retries": -1})
        with pytest.raises(ValueError, match="replan_top_k"):
            ElasticConfig.from_config({"replan_top_k": 0})

    def test_int_knobs_reject_bool_float_and_bad_strings(self):
        # int(True) == 1 and int(2.9) == 2 would silently run a misconfigured
        # knob — the contract says ill-typed values raise, with the knob name
        with pytest.raises(ValueError, match="replan_top_k.*integer"):
            ElasticConfig.from_config({"replan_top_k": True})
        with pytest.raises(ValueError, match="save_retries.*integer"):
            ElasticConfig.from_config({"save_retries": 2.9})
        with pytest.raises(ValueError, match="save_retries.*integer"):
            ElasticConfig.from_config({"save_retries": "lots"})
        with pytest.raises(ValueError, match="grace_period_seconds.*number"):
            ElasticConfig.from_config({"grace_period_seconds": "fast"})
        with pytest.raises(ValueError, match="grace_period_seconds.*number"):
            ElasticConfig.from_config({"grace_period_seconds": True})
        # ints are fine for float knobs; floats are not for int knobs
        assert ElasticConfig.from_config(
            {"grace_period_seconds": 5}).grace_period_seconds == 5.0

    def test_checkpoint_config_knobs_flow_through_elastic_config(self):
        # one source of truth: the checkpointer's retry knobs parse via the
        # validated ElasticConfig block, not re-read with literal defaults
        cc = CheckpointConfig.from_config({"exp_manager": {"elastic": {
            "save_retries": 7, "save_retry_backoff_seconds": 0.25}}})
        assert cc.save_retries == 7
        assert cc.save_retry_backoff_seconds == 0.25
        default = ElasticConfig()
        cc = CheckpointConfig.from_config({})
        assert cc.save_retries == default.save_retries
        assert cc.save_retry_backoff_seconds == \
            default.save_retry_backoff_seconds
        with pytest.raises(ValueError, match="save_retries"):
            CheckpointConfig.from_config(
                {"exp_manager": {"elastic": {"save_retries": "lots"}}})

    def test_loader_validates_the_block(self):
        # a typo'd knob must die at config load, not silently run defaults
        with pytest.raises(ValueError, match="grace_period_seconds"):
            load_config({"exp_manager": {"elastic": {"grace_perid_seconds": 5}}})
        cfg = load_config({"exp_manager": {"elastic": {"enabled": True}}})
        assert cfg.exp_manager.elastic.enabled


# ---------------------------------------------------------------------------
# transient-error classification + save retry
# ---------------------------------------------------------------------------


class TestTransientClassification:
    def test_direct_oserrors(self):
        assert is_transient_save_error(OSError(errno.ENOSPC, "disk full"))
        assert is_transient_save_error(OSError(errno.EIO, "io"))
        assert not is_transient_save_error(
            OSError(errno.EACCES, "permission"))
        assert not is_transient_save_error(ValueError("bad tree"))

    def test_wrapped_cause_chain(self):
        # orbax wraps the underlying OSError in its own exception types
        try:
            try:
                raise OSError(errno.ENOSPC, "disk full")
            except OSError as inner:
                raise RuntimeError("commit failed") from inner
        except RuntimeError as outer:
            assert is_transient_save_error(outer)

    def test_timeout_is_transient(self):
        assert is_transient_save_error(TimeoutError("slow store"))


def _small_state(step=1, scale=1.0):
    params = {"w": jnp.full((8, 4), scale, jnp.float32)}
    opt = {"mu": {"w": jnp.zeros((8, 4), jnp.float32)},
           "step": jnp.asarray(step)}
    return TrainState(params=params, opt_state=opt, step=step,
                      consumed_samples=step * 8)


class TestSaveRetry:
    def test_transient_failures_retry_then_succeed(self, tmp_path,
                                                   monkeypatch):
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        real_save = ck.save
        calls = {"n": 0}

        def flaky(state, **kw):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise OSError(errno.ENOSPC, "injected disk full")
            return real_save(state, **kw)

        monkeypatch.setattr(ck, "save", flaky)
        slept = []
        monkeypatch.setattr(
            "neuronx_distributed_training_tpu.checkpoint.manager.time.sleep",
            slept.append)
        assert ck.save_with_retry(_small_state(step=3), retries=3,
                                  backoff_seconds=0.25)
        assert calls["n"] == 3
        assert slept == [0.25, 0.5]  # exponential backoff, doubled per retry
        assert ck.latest_step() == 3
        ck.close()

    def test_non_transient_raises_immediately(self, tmp_path, monkeypatch):
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        calls = {"n": 0}

        def bad(state, **kw):
            calls["n"] += 1
            raise ValueError("programming error")

        monkeypatch.setattr(ck, "save", bad)
        with pytest.raises(ValueError, match="programming error"):
            ck.save_with_retry(_small_state(), retries=5, backoff_seconds=0.0)
        assert calls["n"] == 1
        ck.close()

    def test_exhausted_retries_reraise_last_transient(self, tmp_path,
                                                      monkeypatch):
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        calls = {"n": 0}

        def always_enospc(state, **kw):
            calls["n"] += 1
            raise OSError(errno.ENOSPC, "injected")

        monkeypatch.setattr(ck, "save", always_enospc)
        with pytest.raises(OSError, match="injected"):
            ck.save_with_retry(_small_state(), retries=2, backoff_seconds=0.0)
        assert calls["n"] == 3  # first attempt + 2 retries
        ck.close()

    def test_deadline_bounds_the_grace_window(self, tmp_path, monkeypatch):
        import time as _time

        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        calls = {"n": 0}

        def always_enospc(state, **kw):
            calls["n"] += 1
            raise OSError(errno.ENOSPC, "injected")

        monkeypatch.setattr(ck, "save", always_enospc)
        with pytest.raises(OSError):
            ck.save_with_retry(_small_state(), retries=10,
                               backoff_seconds=60.0,
                               deadline=_time.monotonic() + 0.1)
        assert calls["n"] == 1  # no 60 s sleep past the expired notice
        ck.close()

    def test_failed_save_never_shadows_last_good(self, tmp_path, monkeypatch):
        """Regression: a failed step-5 save must leave step 3 restorable —
        no stale staging dirs, latest_step still the committed one."""
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        good = _small_state(step=3, scale=2.0)
        assert ck.save(good)
        ck.wait()

        real_save = ck.save

        def fails_midway(state, **kw):
            # simulate a crash mid-write: orbax leaves a staging dir behind
            (ck.directory / "5.orbax-checkpoint-tmp-99").mkdir()
            raise OSError(errno.ENOSPC, "injected mid-write")

        monkeypatch.setattr(ck, "save", fails_midway)
        with pytest.raises(OSError):
            ck.save_with_retry(_small_state(step=5), retries=1,
                               backoff_seconds=0.0)
        monkeypatch.setattr(ck, "save", real_save)
        assert not list(ck.directory.glob("5.orbax-checkpoint-tmp-*")), \
            "partial-save staging dir survived cleanup"
        assert ck.latest_step() == 3
        restored = ck.restore(good.params, good.opt_state)
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(good.params["w"]))
        ck.close()

    def test_cleanup_sweeps_previous_steps_staging_dirs(self, tmp_path,
                                                        monkeypatch):
        """An async commit failure surfaces at the NEXT save() call — i.e.
        for a later step.  The cleanup must sweep the earlier step's
        staging leftovers too, not just the step it was called for."""
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        # step 10's background commit died mid-write and left its staging
        # tree; the error will surface at the step-20 save below
        (ck.directory / "10.orbax-checkpoint-tmp-7").mkdir()

        def fails(state, **kw):
            raise OSError(errno.ENOSPC, "surfaced stale async failure")

        monkeypatch.setattr(ck, "save", fails)
        with pytest.raises(OSError):
            ck.save_with_retry(_small_state(step=20), retries=0,
                               backoff_seconds=0.0)
        assert not list(ck.directory.glob("*.orbax-checkpoint-tmp-*")), \
            "previous step's staging dir survived the sweep"
        ck.close()


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------


def _tiny_raw(tmp_path, **over):
    raw = tiny_llama_config(tmp_path, max_steps=4, save_every=2)
    raw.update(over)
    return raw


class TestManifest:
    def test_build_manifest_fields(self, tmp_path, cpu_mesh):
        cfg = load_config(_tiny_raw(tmp_path))
        mf = build_manifest(cfg, cpu_mesh, step=7, schedule=None,
                            model_family="LlamaConfig", save_bf16=False)
        assert mf["world_size"] == 8
        assert mf["plan"]["dp"] == batch_schedule(cfg, 8)["dp_size"]
        assert mf["plan"]["pp"] == 1 and mf["layer_layout"] == "flat"
        assert mf["model"]["num_layers"] == 2
        assert mf["step"] == 7 and not mf["save_bf16"]

    def test_manifest_round_trip_and_absence(self, tmp_path):
        ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                           save_top_k=0))
        st = _small_state(step=2)
        ck.save(st, manifest={"format": 1, "world_size": 4,
                              "plan": {"dp": 4}})
        ck.wait()
        assert ck.read_manifest()["world_size"] == 4
        ck.save(_small_state(step=4))  # no manifest on this one
        ck.wait()
        assert ck.read_manifest(step=4) is None  # pre-elastic save: None
        ck.close()

    def test_discover_checkpoint_dir(self, tmp_path):
        raw = _tiny_raw(tmp_path / "exp")
        cfg = load_config(raw)
        assert discover_checkpoint_dir(cfg) is None  # nothing yet
        name = raw["name"]
        for v in (0, 2):  # newest version_N wins
            (tmp_path / "exp" / name / f"version_{v}" / "checkpoints").mkdir(
                parents=True)
        # an operator's stray non-numeric dir must be ignored, not crash
        (tmp_path / "exp" / name / "version_backup_7").mkdir()
        got = discover_checkpoint_dir(cfg)
        assert got is not None and got.parts[-2] == "version_2"

    def test_discover_mirrors_exp_manager_selection(self, tmp_path):
        """Discovery must key the replan to the dir ExpManager will ACTUALLY
        resume from: its selection is newest version_N with NO
        has-checkpoints fallback, and no resume at all when
        ``resume_if_exists`` is off."""
        raw = _tiny_raw(tmp_path / "exp")
        name = raw["name"]
        (tmp_path / "exp" / name / "version_0" / "checkpoints").mkdir(
            parents=True)
        # a later run crashed before any save: version_1 has no checkpoints/
        # — ExpManager resumes version_1 (fresh), so discovery finds nothing
        (tmp_path / "exp" / name / "version_1").mkdir()
        assert discover_checkpoint_dir(load_config(raw)) is None
        # resume_if_exists off: a fresh version dir is opened, nothing binds
        raw2 = dict(raw)
        raw2["exp_manager"] = dict(raw["exp_manager"],
                                   resume_if_exists=False)
        (tmp_path / "exp" / name / "version_1").rmdir()
        assert discover_checkpoint_dir(load_config(raw2)) is None


# ---------------------------------------------------------------------------
# layout compatibility + replanning
# ---------------------------------------------------------------------------


class TestPlanLayout:
    MANIFEST = {"plan": {"pp": 1, "vp": 1, "tp": 2, "dp": 4},
                "layer_layout": "flat"}

    def test_tp_dp_changes_are_free(self):
        assert plan_layout_reason(self.MANIFEST,
                                  {"pp": 1, "vp": 1, "tp": 4, "dp": 2}) is None

    def test_pp_change_pins_layout(self):
        reason = plan_layout_reason(self.MANIFEST, {"pp": 2, "vp": 1})
        assert reason is not None and "pipeline" in reason

    def test_vp_change_under_pp_pins_layout(self):
        mf = {"plan": {"pp": 2, "vp": 2}, "layer_layout": "interleaved"}
        assert plan_layout_reason(mf, {"pp": 2, "vp": 1}) is not None
        assert plan_layout_reason(mf, {"pp": 2, "vp": 2}) is None


def _seed_checkpoint_with_manifest(tmp_path, raw, world, plan_over=None):
    """Lay down exp/<name>/version_0/checkpoints with one tiny save carrying
    a manifest for ``world`` chips — the replanner's discovery target."""
    cfg = load_config(raw)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:world])
    manifest = build_manifest(cfg, mesh, step=2, schedule=None,
                              model_family="LlamaConfig", save_bf16=False)
    if plan_over:
        manifest["plan"].update(plan_over)
    em = raw["exp_manager"]
    ck_dir = (os.path.join(str(em["exp_dir"]), raw["name"], "version_0",
                           "checkpoints"))
    os.makedirs(ck_dir, exist_ok=True)
    ck = Checkpointer(CheckpointConfig(dir=ck_dir, async_save=False,
                                       save_top_k=0))
    ck.save(_small_state(step=2), manifest=manifest)
    ck.wait()
    ck.close()
    return cfg


class TestMaybeReplan:
    def test_no_checkpoint_is_a_noop(self, tmp_path):
        cfg = load_config(_tiny_raw(tmp_path))
        result = maybe_replan(cfg, 8)
        assert not result.replanned and result.cfg is cfg

    def test_same_world_skips_replanning(self, tmp_path):
        cfg = _seed_checkpoint_with_manifest(tmp_path, _tiny_raw(tmp_path), 4)
        result = maybe_replan(cfg, 4)
        assert not result.replanned
        assert result.manifest is not None  # but the manifest WAS read

    def test_changed_world_replans_and_records(self, tmp_path):
        cfg = _seed_checkpoint_with_manifest(tmp_path, _tiny_raw(tmp_path), 4)
        result = maybe_replan(cfg, 2)
        assert result.replanned
        rec = result.record
        assert rec["old_world"] == 4 and rec["new_world"] == 2
        assert rec["old_plan"]["dp"] == 4
        assert rec["new_plan"]["dp"] != rec["old_plan"]["dp"]
        # the imposed config is legal on the new world
        sched = batch_schedule(result.cfg, 2)
        assert sched["dp_size"] == rec["new_plan"]["dp"]

    def test_model_identity_mismatch_refuses_resume(self, tmp_path):
        raw = _tiny_raw(tmp_path)
        _seed_checkpoint_with_manifest(tmp_path, raw, 4)
        raw["model"]["num_layers"] = 4  # not the model that was saved
        with pytest.raises(ElasticResumeError, match="num_layers"):
            maybe_replan(load_config(raw), 2)

    def test_impossible_layout_is_a_curated_error(self, tmp_path):
        # manifest claims pp=5: no 2-chip plan can keep that layer layout
        cfg = _seed_checkpoint_with_manifest(tmp_path, _tiny_raw(tmp_path), 4,
                                             plan_over={"pp": 5})
        with pytest.raises(ElasticResumeError, match="layer layout"):
            maybe_replan(cfg, 2)

    def test_lattice_miss_falls_back_to_declared_config(self, tmp_path):
        """vp=3 has no representation in the planner's curated vp lattice;
        the config's OWN declared parallelism (legal on the new world,
        layout-matching) must be accepted instead of refusing the resume —
        this is also what makes a hand-forced --set mesh actionable."""
        raw = _tiny_raw(tmp_path)
        raw["model"]["num_layers"] = 6
        raw["distributed_strategy"].update(
            pipeline_model_parallel_size=2,
            virtual_pipeline_model_parallel_size=3)
        cfg = _seed_checkpoint_with_manifest(tmp_path, raw, 8)
        result = maybe_replan(cfg, 4)
        assert result.replanned
        assert result.record["fallback"] == "declared-config"
        assert result.record["new_plan"]["pp"] == 2
        assert result.record["new_plan"]["vp"] == 3
        assert result.record["new_plan"]["dp"] == 2
        assert result.cfg is cfg  # the declared config IS the plan


# ---------------------------------------------------------------------------
# fault injector + drain-on-teardown
# ---------------------------------------------------------------------------


class TestFaultInjector:
    def test_validation(self):
        with pytest.raises(ValueError, match="kill|sigterm"):
            FaultInjector(at_step=1, mode="explode")
        with pytest.raises(ValueError, match="step|save|restore"):
            FaultInjector(at_step=1, phase="nowhere")

    def test_fires_once_at_phase_and_step(self):
        fi = FaultInjector(at_step=3, mode="sigterm", phase="save")
        assert not fi.maybe_fire("step", 3)    # wrong phase
        assert not fi.maybe_fire("save", 2)    # too early
        assert fi.maybe_fire("save", 3)
        assert fi.fired and not fi.maybe_fire("save", 4)  # once only

    def test_kill_mode_raises(self):
        fi = FaultInjector(at_step=1, mode="kill", phase="step")
        with pytest.raises(SimulatedPreemption):
            fi.maybe_fire("step", 1)


class TestDrainOnTeardown:
    def test_kill_mid_async_save_is_not_orphaned(self, tmp_path, devices8):
        """fit() dies right after an ASYNC save was initiated; the teardown
        drain (wait_until_finished on every exit path) must still commit it —
        the next incarnation resumes from step 2, not step 0."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=6, save_every=2)
        cfg = load_config(raw)
        t = Trainer.from_config(cfg, devices=devices8[:4])
        t.fault_injector = FaultInjector(at_step=2, mode="kill", phase="save")
        with pytest.raises(SimulatedPreemption):
            t.fit()
        ck_dir = discover_checkpoint_dir(cfg)
        assert ck_dir is not None
        ck = Checkpointer(CheckpointConfig(dir=str(ck_dir), async_save=False,
                                           save_top_k=0))
        try:
            assert ck.latest_step() == 2, (
                "async save orphaned by the injected kill")
            assert ck.read_manifest()["world_size"] == 4
        finally:
            ck.close()


class TestGraceWindowStopPath:
    def test_stop_on_cadence_step_takes_drained_emergency_save(
            self, tmp_path, devices8):
        """A preemption stop landing exactly on the checkpoint cadence must
        still take the drained, deadline-bounded emergency save — a plain
        async cadence save has no drain, no retry deadline, and therefore no
        grace-window guarantee."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=6, save_every=2)
        cfg = load_config(raw)
        t = Trainer.from_config(cfg, devices=devices8[:4])
        # notice before the step at counter 1 -> that step still runs -> the
        # stop boundary is step 2, which IS the save_every=2 cadence
        t.fault_injector = FaultInjector(at_step=1, mode="sigterm",
                                         phase="step")
        calls = []
        real = t.checkpointer.save_with_retry

        def spy(state, **kw):
            calls.append({"step": state.step, "force": kw.get("force"),
                          "drain": kw.get("drain"),
                          "deadline": kw.get("deadline")})
            return real(state, **kw)

        t.checkpointer.save_with_retry = spy
        t.fit()
        at_stop = [c for c in calls if c["step"] == 2]
        assert len(at_stop) == 1, (
            f"expected exactly the emergency save at the stop step, "
            f"got {calls}")
        assert at_stop[0]["force"] and at_stop[0]["drain"], (
            "the stop-step save was the undrained cadence save — the "
            "grace-window guarantee is lost")
        assert at_stop[0]["deadline"] is not None

    def test_sigterm_during_cadence_save_does_not_double_save(
            self, tmp_path, devices8):
        """The SIGTERM handler can run at any bytecode — including inside
        the cadence save itself.  The stop decision must be snapshotted
        before that save, or the stop branch re-saves the same step and
        orbax raises StepAlreadyExistsError, turning a graceful preemption
        into a crash.  The notice landing mid-save stops at the NEXT
        boundary instead."""
        import signal as _sig

        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=6, save_every=2)
        t = Trainer.from_config(load_config(raw), devices=devices8[:4])
        real = t.checkpointer.save_with_retry
        fired = {"done": False}

        def racy(state, **kw):
            out = real(state, **kw)
            if state.step == 2 and not fired["done"]:
                # synchronous delivery: the fit loop's handler sets the stop
                # reason "mid-save", after this save already ran
                fired["done"] = True
                _sig.raise_signal(_sig.SIGTERM)
            return out

        t.checkpointer.save_with_retry = racy
        t.fit()  # must not raise StepAlreadyExistsError
        # the notice was honored one boundary later, with the emergency save
        assert t.step == 3

    def test_notice_during_final_save_is_recorded(self, tmp_path, devices8):
        """A sigterm-mode notice landing during the run's LAST save has no
        loop iteration left to convert it — it must land in the elastic
        trail's stop_reason, not vanish."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=2, save_every=2)
        t = Trainer.from_config(load_config(raw), devices=devices8[:4])
        t.fault_injector = FaultInjector(at_step=2, mode="sigterm",
                                         phase="save")
        t.fit()
        assert t.fault_injector.fired
        with open(os.path.join(_run_dir_of(raw), "run_summary.json")) as f:
            summary = json.load(f)
        assert "mid-save" in summary["elastic"]["stop_reason"]

    def test_restore_failure_still_tears_down(self, tmp_path, devices8):
        """A restore-phase kill (or any corrupt-checkpoint restore failure)
        happens before the fit loop proper — it must still restore the
        SIGTERM handler and close the exp manager (log FileHandler), or
        every faulted incarnation leaks both."""
        import logging as _logging
        import signal as _sig

        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=4, save_every=2)
        t1 = Trainer.from_config(load_config(raw), devices=devices8[:4])
        t1.fit()  # leaves a resumable checkpoint
        before_handler = _sig.getsignal(_sig.SIGTERM)
        n_log_handlers = len(_logging.getLogger().handlers)
        t2 = Trainer.from_config(load_config(raw), devices=devices8[:4])
        t2.fault_injector = FaultInjector(at_step=0, mode="kill",
                                          phase="restore")
        with pytest.raises(SimulatedPreemption):
            t2.fit()
        assert _sig.getsignal(_sig.SIGTERM) is before_handler, (
            "SIGTERM handler leaked by the faulted restore")
        assert len(_logging.getLogger().handlers) == n_log_handlers, (
            "exp manager log handler leaked by the faulted restore")

    def test_sigterm_mid_save_notice_stops_the_run(self, tmp_path, devices8):
        """FaultInjector(mode=sigterm, phase=save): the notice fired during a
        cadence save must stop the run with an emergency checkpoint — not be
        silently swallowed (the run completing all steps would mean the
        injection exercised nothing)."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=6, save_every=2)
        t = Trainer.from_config(load_config(raw), devices=devices8[:4])
        t.fault_injector = FaultInjector(at_step=2, mode="sigterm",
                                         phase="save")
        t.fit()
        assert t.fault_injector.fired
        # notice during the step-2 cadence save -> one more step runs ->
        # emergency stop at step 3, well short of max_steps
        assert t.step == 3
        with open(os.path.join(_run_dir_of(raw), "run_summary.json")) as f:
            summary = json.load(f)
        assert "mid-save" in summary["elastic"]["stop_reason"]


# ---------------------------------------------------------------------------
# resharding restore across dp changes (the ZeRO-1 regrouping)
# ---------------------------------------------------------------------------


def _llama_trees(tied: bool, mesh):
    """Tiny REAL llama params + full opt state (mu/nu/master/ema/health) with
    the production ZeRO-1 specs on ``mesh`` — global shapes are mesh-free, so
    the same call serves the save and the (differently sized) restore mesh."""
    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.optim.adamw import (
        init_opt_state,
        opt_state_specs,
    )
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

    mc = llama.LlamaConfig.from_config(
        {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
         "num_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
         "max_position_embeddings": 32, "tie_word_embeddings": tied}, {})
    # bf16 params + f32 optimizer: the ONLY regime with a distinct fp32
    # master tree (mixed_precision keeps params in f32 and skips it)
    policy = DtypePolicy.from_precision_config({"type": "bf16"})
    params = llama.init_params(jax.random.PRNGKey(0), mc, policy)
    pspecs = llama.param_specs(mc)
    opt = init_opt_state(params, policy=policy, ema=True, health=True)
    ospecs = opt_state_specs(params, pspecs, mesh, zero1=True, policy=policy,
                             ema=True, health=True)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda x: isinstance(x, P))
    return place(params, pspecs), place(opt, ospecs), pspecs, mc, policy


@pytest.mark.parametrize("dp_from,dp_to", [(4, 2), (2, 4)])
@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("save_bf16", [False, True])
def test_restore_reshards_across_dp_change(tmp_path, devices8, dp_from,
                                           dp_to, tied, save_bf16):
    """Params, ZeRO-1 moments, fp32 master, EMA, and health counters saved at
    dp_from restore direct-to-sharded at dp_to — the dp-shard regrouping is
    orbax's sharding-aware read against the NEW mesh's specs."""
    from neuronx_distributed_training_tpu.optim.adamw import opt_state_specs

    mesh_from = build_mesh(MeshConfig(), devices=devices8[:dp_from])
    mesh_to = build_mesh(MeshConfig(), devices=devices8[:dp_to])
    params, opt, pspecs, mc, policy = _llama_trees(tied, mesh_from)
    assert "master" in opt and "ema" in opt and "health" in opt
    assert tied == ("lm_head" not in params)

    ck = Checkpointer(CheckpointConfig(dir=tmp_path, async_save=False,
                                       save_top_k=0, save_bf16=save_bf16))
    ck.save(TrainState(params, opt, 5, 40))
    ck.wait()
    ospecs_to = opt_state_specs(params, pspecs, mesh_to, zero1=True,
                                policy=policy, ema=True, health=True)
    restored = ck.restore(params, opt, mesh=mesh_to, param_specs=pspecs,
                          opt_specs=ospecs_to)
    ck.close()
    assert restored.step == 5 and restored.consumed_samples == 40

    def assert_on_new_mesh(tree, specs):
        def one(x, s):
            assert x.sharding.mesh.devices.size == dp_to, (
                f"leaf not resharded onto the {dp_to}-device mesh")
            assert x.sharding.spec == s
        jax.tree_util.tree_map(one, tree, specs,
                               is_leaf=lambda x: isinstance(x, P))

    assert_on_new_mesh(restored.params, pspecs)
    assert_on_new_mesh(restored.opt_state, ospecs_to)
    for key in ("mu", "nu", "master", "ema", "health"):
        assert key in restored.opt_state
    tol = dict(rtol=1e-2, atol=1e-2) if save_bf16 else dict(rtol=0, atol=0)
    np.testing.assert_allclose(
        np.asarray(restored.params["embed"]["embedding"], np.float32),
        np.asarray(params["embed"]["embedding"], np.float32), **tol)
    # the fp32 master + EMA trees are exact either way (save_bf16 only
    # downcasts the PARAMS item; opt state keeps full precision)
    np.testing.assert_array_equal(
        np.asarray(restored.opt_state["master"]["layers"]["attn"]["qkv"]["w"]),
        np.asarray(opt["master"]["layers"]["attn"]["qkv"]["w"]))
    np.testing.assert_array_equal(
        np.asarray(restored.opt_state["ema"]["embed"]["embedding"]),
        np.asarray(opt["ema"]["embed"]["embedding"]))
    if not tied:
        np.testing.assert_allclose(
            np.asarray(restored.params["lm_head"]["w"], np.float32),
            np.asarray(params["lm_head"]["w"], np.float32), **tol)


# ---------------------------------------------------------------------------
# the drill (the PR's acceptance criterion, automated)
# ---------------------------------------------------------------------------


class TestDrill:
    def test_kill_and_resume_at_smaller_dp(self, tmp_path, devices8):
        """Tiny-llama killed at step 3, resumed on dp 2 (was 4): replanned
        mesh recorded, loss trajectory continuous at pinned tolerance,
        restart cost in goodput accounting."""
        report = run_drill(tmp_path, at_step=3, phase="step", mode="kill",
                           world=4, resume_world=2, total_steps=6)
        assert report["ok"] and report["replanned"]
        assert report["old_plan"]["dp"] == 4
        assert report["new_plan"]["dp"] == 2
        assert report["max_loss_diff"] <= report["loss_tol"]
        assert report["goodput_fraction"] is not None
        assert report["restart_cost_seconds"] >= 0.0
        # the replanned mesh is durably recorded in run_summary.json
        with open(os.path.join(report["run_dir"], "run_summary.json")) as f:
            summary = json.load(f)
        assert summary["elastic"]["replan"]["new_plan"]["dp"] == 2

    @pytest.mark.slow
    def test_sigterm_grace_window_same_world(self, tmp_path, devices8):
        """Graceful preemption notice: the emergency checkpoint inside the
        grace window makes the same-world resume bitwise."""
        report = run_drill(tmp_path, at_step=2, phase="step", mode="sigterm",
                           world=4, resume_world=4, total_steps=6)
        assert report["ok"] and not report["replanned"]
        assert report["max_param_diff"] == 0.0  # bitwise at same world
        # the notice lands before the step at counter 2; that step still
        # runs, then the boundary takes the EMERGENCY save at step 3 — an
        # odd step, so the save_every=2 periodic cadence cannot have taken it
        assert report["resume_step"] == 3

    @pytest.mark.slow
    def test_kill_and_resume_at_larger_dp(self, tmp_path, devices8):
        report = run_drill(tmp_path, at_step=3, phase="step", mode="kill",
                           world=2, resume_world=4, total_steps=6)
        assert report["ok"] and report["replanned"]
        assert report["old_plan"]["dp"] == 2

    @pytest.mark.slow
    def test_restore_phase_drill_kill(self, tmp_path, devices8):
        """The CLI restore drill (--phase restore --mode kill): the fault
        rides the first RESUME incarnation (a fresh start never restores),
        dies mid-restore leaving the save intact, and the second resume
        completes the run bitwise at the same world."""
        report = run_drill(tmp_path, at_step=3, phase="restore", mode="kill",
                           world=2, resume_world=2, total_steps=6)
        assert report["ok"] and not report["replanned"]
        assert report["max_param_diff"] == 0.0

    @pytest.mark.slow
    def test_restore_phase_drill_sigterm_cross_world(self, tmp_path,
                                                     devices8):
        """--phase restore --mode sigterm across a shrink: the notice lands
        mid-restore on the replanned incarnation, which emergency-saves and
        hands off to a clean resume — continuity still holds."""
        report = run_drill(tmp_path, at_step=3, phase="restore",
                           mode="sigterm", world=4, resume_world=2,
                           total_steps=6)
        assert report["ok"] and report["replanned"]
        assert report["new_plan"]["dp"] == 2

    @pytest.mark.slow
    def test_kill_mid_restore_leaves_save_intact(self, tmp_path, devices8):
        """A kill DURING restore (checkpoint read, state not yet applied)
        must leave the save untouched — the next attempt succeeds."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        raw = tiny_llama_config(tmp_path, max_steps=6, save_every=2)
        cfg = load_config(raw)
        t1 = Trainer.from_config(cfg, devices=devices8[:4])
        t1.fault_injector = FaultInjector(at_step=4, mode="kill",
                                          phase="step")
        with pytest.raises(SimulatedPreemption):
            t1.fit()
        # incarnation 2 dies mid-restore
        t2 = Trainer.from_config(load_config(raw), devices=devices8[:4])
        t2.fault_injector = FaultInjector(at_step=0, mode="kill",
                                          phase="restore")
        with pytest.raises(SimulatedPreemption):
            t2.fit()
        # incarnation 3 resumes cleanly from the same save
        t3 = Trainer.from_config(load_config(raw), devices=devices8[:4])
        m = t3.fit()
        assert np.isfinite(m["loss"])
        losses = read_losses(_run_dir_of(raw))
        assert max(losses) == 6


def _run_dir_of(raw):
    em = raw["exp_manager"]
    return os.path.join(str(em["exp_dir"]), raw["name"], "version_0")


@pytest.mark.slow
def test_same_world_autotune_respects_checkpoint_layout(tmp_path, devices8,
                                                        monkeypatch):
    """``--autotune`` on a SAME-world resume must not impose a mesh that
    breaks the resumable checkpoint's layer layout: the planner's winner is
    filtered to layout-compatible candidates (or the launch refuses with a
    curated exit) — never an opaque restore-shape crash."""
    import yaml

    from neuronx_distributed_training_tpu.trainer import cli

    raw = tiny_llama_config(tmp_path / "exp", max_steps=4, save_every=2)
    raw["distributed_strategy"]["pipeline_model_parallel_size"] = 2
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml.safe_dump(raw))
    monkeypatch.setattr(sys, "argv", ["nxdt-train", "--config", str(p)])
    cli.main()  # run 1: saves a pp=2 checkpoint
    monkeypatch.setattr(
        sys, "argv", ["nxdt-train", "--config", str(p), "--autotune"])
    try:
        cli.main()  # same world, planner on: pp=1 winner must be filtered
    except SystemExit as e:
        assert "layer layout" in str(e)
    else:
        # resumed without a restore-shape crash; run 1's trajectory intact
        losses = read_losses(_run_dir_of(raw))
        assert max(losses) == 4 and np.isfinite(losses[4])


# ---------------------------------------------------------------------------
# report surfaces: metrics_report elastic trail
# ---------------------------------------------------------------------------


_SUMMARY_WITH_TRAIL = {
    "goodput": {"goodput_fraction": 0.91},
    "elastic": {
        "resumed": True,
        "restart_seconds": 4.312,
        "replan_seconds": 1.807,
        "stop_reason": "SIGTERM (preemption)",
        "replan": {
            "old_world": 4, "new_world": 2, "checkpoint_step": 2,
            "old_plan": {"dp": 4, "tp": 1, "pp": 1, "micro_batch_size": 1},
            "new_plan": {"dp": 2, "tp": 1, "pp": 1, "micro_batch_size": 1},
            "predicted_step_seconds": 0.125,
            "skipped_incompatible": 1,
        },
    },
}


class TestReportSurfaces:
    def test_metrics_report_renders_elastic_trail(self, tmp_path):
        import metrics_report

        out = metrics_report.elastic_section(_SUMMARY_WITH_TRAIL)
        assert "restart/replan trail" in out
        assert "world 4 -> 2 chips" in out
        assert "dp=4" in out and "dp=2" in out
        assert "SIGTERM (preemption)" in out
        assert "1 layout-incompatible" in out
        # and through the full render() path from a run dir on disk
        (tmp_path / "run_summary.json").write_text(
            json.dumps(_SUMMARY_WITH_TRAIL))
        rendered = metrics_report.render(
            None, str(tmp_path / "run_summary.json"))
        assert "restart/replan trail" in rendered

    def test_metrics_report_no_trail_no_section(self):
        import metrics_report

        assert metrics_report.elastic_section({}) == ""
        assert metrics_report.elastic_section({"elastic": {}}) == ""
