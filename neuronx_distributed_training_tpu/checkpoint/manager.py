"""Orbax-backed checkpoint manager.

Capability map to the reference (SURVEY.md §5.4):

- per-rank sharded save / tensor streaming (``save_xser``/``load_xser``,
  reference ``nlp_overrides.py:1141-1155``)      -> Orbax OCDBT/TensorStore,
  every process writes its own shards, restore is sharding-aware;
- ``async_checkpointing`` (forked writer process, ``known_issues.rst:53-81``)
  -> Orbax async checkpointing (background thread + commit future);
- top-k retention + auto-delete (``config_overview.rst:243-249``)
  -> ``max_to_keep`` + ``best_fn`` on the monitored metric;
- auto-resume from newest checkpoint (``exp_manager.py:333-404``)
  -> ``latest_step()`` + ``restore``;
- filename-encoded ``consumed_samples`` (``data/base.py:40-47``)
  -> explicit ``meta`` JSON item per step (no regex parsing needed; the value
  rides inside the checkpoint);
- ``weight_init_only`` warm start (``nlp_overrides.py:541-568``)
  -> ``restore_params_only``.
"""

from __future__ import annotations

import dataclasses
import errno
import logging
import time
from pathlib import Path
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.checkpoint import integrity as ck_integrity
from neuronx_distributed_training_tpu.checkpoint.integrity import (
    CheckpointIntegrityError,
    IntegrityConfig,
    SaveAuditor,
)
from neuronx_distributed_training_tpu.telemetry.spans import timed_import

logger = logging.getLogger(__name__)

#: errno values treated as TRANSIENT save-I/O failures (full disk being
#: cleaned by retention, a flaky NFS/FUSE mount, an object-store hiccup) —
#: worth a bounded retry with backoff.  Anything else (bad tree, permission,
#: programming error) re-raises immediately.
TRANSIENT_SAVE_ERRNOS = frozenset({
    errno.ENOSPC, errno.EIO, errno.EAGAIN, errno.EBUSY, errno.ETIMEDOUT,
    errno.EINTR, errno.EDQUOT,
})


def is_transient_save_error(exc: BaseException) -> bool:
    """Is ``exc`` (or anything in its cause/context chain) a transient I/O
    error worth retrying?  Orbax wraps the underlying ``OSError`` in its own
    exception types, so the chain is walked, not just the top."""
    seen: set[int] = set()
    cur: Optional[BaseException] = exc
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if isinstance(cur, TimeoutError):
            return True
        if isinstance(cur, OSError) and cur.errno in TRANSIENT_SAVE_ERRNOS:
            return True
        cur = cur.__cause__ or cur.__context__
    return False


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Mirrors the reference's ``exp_manager.checkpoint_callback_params`` +
    ``save_xser``/``async_checkpointing`` knobs (``config_overview.rst:243-308``)."""

    dir: str | Path = "checkpoints"
    save_top_k: int = 3
    every_n_train_steps: int = 100
    async_save: bool = True
    monitor: str = "loss"  # metric whose *lowest* value defines "best"
    # reference exp_manager.save_bf16 (exp_manager.py:58): store model weights
    # in bf16 — halves params bytes; restore casts back up (resume is no
    # longer bitwise, the knob's inherent trade)
    save_bf16: bool = False
    # reference checkpoint_callback_params.use_master_weights_in_ckpt
    # (exp_manager.py:46, base.py:131): keep the fp32 master copy in the
    # checkpoint.  Default True here (bitwise resume); False drops the master
    # tree from the save and restore re-seeds it from the saved params.
    use_master_weights_in_ckpt: bool = True
    # elastic-resume hardening (``exp_manager.elastic``, docs/elasticity.md):
    # bounded retry with exponential backoff on TRANSIENT save I/O errors
    # (ENOSPC/EIO/...), with partial-save cleanup so a failed save never
    # shadows the last good one
    save_retries: int = 3
    save_retry_backoff_seconds: float = 0.5
    # checkpoint-integrity policy (``exp_manager.checkpoint.integrity``,
    # docs/elasticity.md "Integrity & walk-back"): digest sidecar in every
    # save, verified restore with walk-back + quarantine, optional
    # post-commit read-back audit
    integrity: IntegrityConfig = dataclasses.field(
        default_factory=IntegrityConfig)

    @classmethod
    def from_config(cls, cfg: dict[str, Any]) -> "CheckpointConfig":
        em = dict(cfg.get("exp_manager", {}) or {})
        cb = dict(em.get("checkpoint_callback_params", {}) or {})
        # retry knobs flow through the validated exp_manager.elastic block —
        # ElasticConfig owns the defaults (trainer/elastic.py ELASTIC_KNOBS),
        # so the checkpointer cannot diverge from the documented knob block
        from neuronx_distributed_training_tpu.trainer.elastic import (
            ElasticConfig,
        )

        el = ElasticConfig.from_config(em.get("elastic"))
        return cls(
            dir=em.get("explicit_log_dir") or em.get("exp_dir") or "checkpoints",
            save_top_k=int(cb.get("save_top_k", 3)),
            every_n_train_steps=int(cb.get("every_n_train_steps", 100)),
            async_save=bool(cb.get("async_checkpointing", em.get("async_checkpointing", True))),
            monitor=str(cb.get("monitor", "loss")),
            save_bf16=bool(em.get("save_bf16", cb.get("save_bf16", False))),
            use_master_weights_in_ckpt=bool(
                cb.get("use_master_weights_in_ckpt", True)),
            save_retries=el.save_retries,
            save_retry_backoff_seconds=el.save_retry_backoff_seconds,
            integrity=ck_integrity.parse_checkpoint_block(em.get("checkpoint")),
        )


@dataclasses.dataclass
class TrainState:
    """Everything a resume needs (the reference spreads this across the PTL
    checkpoint dict, loop progress, and the ckpt filename)."""

    params: Any
    opt_state: Any
    step: int
    consumed_samples: int
    extra: dict[str, Any] = dataclasses.field(default_factory=dict)


def resolve_checkpoint_dir(d: str | Path):
    """Local paths -> absolute ``pathlib.Path``; remote URIs (``gs://`` etc.)
    -> ``etils.epath.Path`` so Orbax streams through TensorStore instead of
    silently writing a local directory literally named ``gs:`` (the failure
    mode of ``Path(uri).absolute()``)."""
    s = str(d)
    if "://" not in s:
        return Path(s).absolute()
    from etils import epath

    try:
        return epath.Path(s)
    except KeyError as e:
        raise ValueError(
            f"unsupported checkpoint URI scheme in {s!r}; epath supports "
            f"gs:// and s3:// (local paths need no scheme)"
        ) from e


def _abstract_like(tree: Any, specs: Any, mesh: Optional[Mesh]) -> Any:
    """ShapeDtypeStruct pytree (with shardings when a mesh is given) for
    sharding-aware restore."""

    def one(x, s):
        sharding = NamedSharding(mesh, s) if mesh is not None else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(
        one, tree, specs, is_leaf=lambda x: isinstance(x, P)
    )


def _abstract_from_tree(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
        tree,
    )


def _bf16_read_templates(abs_tree: Any) -> Any:
    """Downcast floating abstract leaves to bf16 — the on-disk dtype of a
    ``save_bf16`` checkpoint (integer leaves, e.g. opt step, untouched)."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=a.sharding)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        abs_tree,
    )


def _cast_like(tree: Any, abs_tree: Any) -> Any:
    """Cast restored arrays up to the template dtype/sharding."""
    return jax.tree_util.tree_map(
        lambda x, a: (jax.device_put(x.astype(a.dtype), a.sharding)
                      if a.sharding is not None else x.astype(a.dtype)),
        tree, abs_tree,
    )


class Checkpointer:
    """Save/restore ``TrainState`` with retention + async + auto-resume."""

    def __init__(self, config: CheckpointConfig, *, keep_last: bool = True):
        self.config = config
        # orbax is loaded here, by the first ``Checkpointer`` of the process,
        # and not with the package: a run that builds none never pays for it
        # (``startup.imports_s["orbax.checkpoint"]``)
        with timed_import("orbax.checkpoint"):
            import orbax.checkpoint as ocp
        directory = resolve_checkpoint_dir(config.dir)
        preservation = None
        if config.save_top_k > 0:
            from orbax.checkpoint.checkpoint_managers import (
                preservation_policy as pp,
            )

            def metric_fn(metrics: Any) -> float:
                return float((metrics or {}).get(self.config.monitor, float("inf")))

            policies = [
                # reverse=True keeps the *lowest* metric values (loss-like)
                pp.BestN(get_metric_fn=metric_fn, n=config.save_top_k, reverse=True),
            ]
            if keep_last:
                # "last" must survive top-k eviction for auto-resume correctness
                # (the reference keeps top-k AND last, exp_manager.py:517-579)
                policies.append(pp.LatestN(n=1))
            preservation = pp.AnyPreservationPolicy(policies)

        options = ocp.CheckpointManagerOptions(
            preservation_policy=preservation,
            enable_async_checkpointing=config.async_save,
            save_interval_steps=1,  # step gating is the trainer's job
        )
        self._mgr = ocp.CheckpointManager(directory, options=options)
        #: integrity bookkeeping — the restore/audit trail the trainer
        #: persists into ``run_summary.json``'s ``integrity`` section
        self.integrity_trail: dict[str, Any] = {}
        #: steps saved but not yet handed to the post-commit audit (they
        #: commit at the next ``wait()``/``save()``; the audit only ever sees
        #: COMMITTED steps)
        self._audit_pending: list[int] = []
        self._auditor: Optional[SaveAuditor] = None
        if config.integrity.enabled and config.integrity.audit:
            self._auditor = SaveAuditor(self.directory)

    def _trail(self) -> dict[str, Any]:
        self.integrity_trail.setdefault("quarantined_steps", [])
        self.integrity_trail.setdefault("verify_seconds", 0.0)
        return self.integrity_trail

    @property
    def directory(self):
        """Local dirs as ``pathlib.Path``; remote stores keep orbax's
        ``epath.Path`` — re-wrapping in ``Path()`` would mangle ``gs://``
        into ``gs:/`` and make every ``exists()``/``glob()`` a silent no-op."""
        d = self._mgr.directory
        return d if "://" in str(d) else Path(str(d))

    # -- save ---------------------------------------------------------------

    def save(
        self,
        state: TrainState,
        *,
        metrics: Optional[dict[str, float]] = None,
        force: bool = False,
        manifest: Optional[dict[str, Any]] = None,
    ) -> bool:
        """Schedule (async) or perform (sync) one save.

        ``manifest`` — the world-size-agnostic topology/plan manifest
        (``trainer.elastic.build_manifest``): mesh axes, parallelism plan,
        model identity.  Stored as its own JSON item so a restart can read
        it WITHOUT templates (the restart-time replanner does exactly that
        before any model state exists).

        When integrity is enabled the save also carries the ``integrity``
        digest sidecar (docs/elasticity.md "Integrity & walk-back"), and —
        with the post-commit audit on — previously COMMITTED steps are
        handed to the background auditor here, with any finished
        audit-failure verdict applied (quarantine) before the new save
        starts.  The verdict application is a non-blocking snapshot: an
        audit still in flight never delays (or deadlocks) a save, emergency
        or periodic."""
        import orbax.checkpoint as ocp

        if self._auditor is not None:
            # the implicit wait also commits any in-flight async save, so
            # the steps kicked to the auditor are guaranteed on disk; orbax
            # would serialize on the previous save here anyway
            self._mgr.wait_until_finished()
            self._kick_audits()
            self._apply_audit_verdicts()
        params = state.params
        if self.config.save_bf16:
            import jax.numpy as jnp

            params = jax.tree_util.tree_map(
                lambda x: (x.astype(jnp.bfloat16)
                           if jnp.issubdtype(x.dtype, jnp.floating) else x),
                params,
            )
        opt_state = state.opt_state
        if not self.config.use_master_weights_in_ckpt and "master" in opt_state:
            opt_state = {k: v for k, v in opt_state.items() if k != "master"}
        meta = {
            "step": int(state.step),
            "consumed_samples": int(state.consumed_samples),
            # restore branches on these (templates must match what was saved)
            "save_bf16": bool(self.config.save_bf16),
            "master_in_ckpt": "master" in opt_state,
            **{k: v for k, v in state.extra.items()},
        }
        items: dict[str, Any] = {
            "params": ocp.args.StandardSave(params),
            "opt_state": ocp.args.StandardSave(opt_state),
            "meta": ocp.args.JsonSave(meta),
        }
        if manifest is not None:
            items["manifest"] = ocp.args.JsonSave(manifest)
        if self.config.integrity.enabled:
            # digests over the EXACT trees handed to orbax (post save_bf16
            # cast / master drop) so restore verification re-hashes the same
            # bytes it reads back from disk.  COST: a synchronous
            # device->host fetch + hash of the full state on this thread —
            # comparable to the host snapshot an async save itself takes,
            # but paid twice; at very large scale where that matters, turn
            # the sidecar off (integrity.enabled: false) or budget the
            # checkpoint cadence for it (docs/elasticity.md)
            try:
                items[ck_integrity.INTEGRITY_ITEM] = ocp.args.JsonSave(
                    ck_integrity.build_sidecar(
                        step=int(state.step), params=params,
                        opt_state=opt_state, meta=meta, manifest=manifest))
            except Exception as e:  # noqa: BLE001 — a sidecar failure must
                # not block the save itself (the step then restores as
                # legacy/unverified, with the warning)
                logger.warning(
                    "integrity sidecar build failed at step %d (saving "
                    "without): %s", state.step, e)
        saved = self._mgr.save(
            int(state.step),
            args=ocp.args.Composite(**items),
            metrics={k: float(v) for k, v in (metrics or {}).items()},
            force=force,
        )
        if saved and self._auditor is not None:
            self._audit_pending.append(int(state.step))
        return saved

    # -- post-commit save audit --------------------------------------------

    def _kick_audits(self) -> None:
        """Hand every pending (now committed) step to the background
        auditor.  Callers guarantee no async save is in flight."""
        if self._auditor is None:
            return
        pending, self._audit_pending = self._audit_pending, []
        for step in pending:
            self._auditor.schedule(step)

    def _apply_audit_verdicts(self) -> list[int]:
        """Snapshot the auditor's COMPLETED verdicts (non-blocking) and
        quarantine any audit failure.  Safe only when no save is in flight
        (quarantine reloads the manager's step registry)."""
        if self._auditor is None:
            return []
        quarantined: list[int] = []
        trail = self._trail()
        for v in self._auditor.poll():
            if v.status != "corrupt":
                continue
            logger.error(
                "post-commit save audit FAILED for step %d: %s",
                v.step, "; ".join(v.failures[:4]))
            if self.config.integrity.quarantine:
                ck_integrity.apply_quarantine(
                    self.directory, v.step, reason="save-audit",
                    failures=v.failures)
                self._mgr.reload()
                quarantined.append(v.step)
                trail.setdefault("audit_quarantined", []).append(v.step)
                if v.step not in trail["quarantined_steps"]:
                    trail["quarantined_steps"].append(v.step)
            else:
                trail.setdefault("corrupt_steps_unquarantined", [])
                if v.step not in trail["corrupt_steps_unquarantined"]:
                    trail["corrupt_steps_unquarantined"].append(v.step)
        if self._auditor is not None:
            trail["audit"] = self._auditor.stats.to_dict()
        return quarantined

    def save_with_retry(
        self,
        state: TrainState,
        *,
        metrics: Optional[dict[str, float]] = None,
        force: bool = False,
        manifest: Optional[dict[str, Any]] = None,
        retries: Optional[int] = None,
        backoff_seconds: Optional[float] = None,
        deadline: Optional[float] = None,
        drain: bool = False,
    ) -> bool:
        """:meth:`save` with bounded retry + exponential backoff on TRANSIENT
        I/O errors (:func:`is_transient_save_error`), cleaning up the partial
        save between attempts so a failed save never shadows the last good
        checkpoint.

        - ``drain=True`` additionally waits for the async commit INSIDE the
          retry loop, so background write errors count as save failures too —
          the emergency/final-save path uses this; periodic saves keep the
          async overlap and surface commit errors at the next ``wait()``.
        - ``deadline`` (a ``time.monotonic()`` instant) bounds the whole
          attempt sequence — the SIGTERM grace window passes the moment the
          preemption notice expires.  The first attempt always runs.

        Non-transient errors re-raise immediately (after cleanup); exhausted
        retries re-raise the LAST transient error."""
        attempts = 1 + max(int(self.config.save_retries
                               if retries is None else retries), 0)
        delay = float(self.config.save_retry_backoff_seconds
                      if backoff_seconds is None else backoff_seconds)
        last: Optional[BaseException] = None
        for attempt in range(attempts):
            try:
                saved = self.save(state, metrics=metrics, force=force,
                                  manifest=manifest)
                if drain:
                    self.wait()
                return saved
            except Exception as e:  # noqa: BLE001 — classified below
                self._cleanup_failed_save(int(state.step))
                if not is_transient_save_error(e):
                    raise
                last = e
                remaining = attempts - 1 - attempt
                if remaining == 0:
                    break
                if deadline is not None and time.monotonic() + delay >= deadline:
                    logger.warning(
                        "checkpoint save at step %d: grace deadline reached "
                        "after attempt %d/%d", state.step, attempt + 1, attempts,
                    )
                    break
                logger.warning(
                    "checkpoint save at step %d failed transiently (%s: %s); "
                    "retrying in %.2fs (%d attempt%s left)",
                    state.step, type(e).__name__, e, delay, remaining,
                    "s" if remaining != 1 else "",
                )
                time.sleep(delay)
                delay *= 2.0
        assert last is not None
        raise last

    def _cleanup_failed_save(self, step: int) -> None:
        """Best-effort removal of a failed save's leftovers so the next
        attempt (or the next run's auto-resume) sees only COMMITTED steps:
        orbax writes into ``<step>.orbax-checkpoint-tmp-*`` staging dirs and
        renames on commit, so stale staging dirs (plus an uncommitted final
        ``<step>`` dir with no commit marker under an interrupted rename)
        are the two shadows to clear.  ``latest_step`` ignores tmp dirs, but
        a crashed retry loop must not leave the directory accumulating
        half-written staging trees on a full disk.

        The error a ``save()`` call surfaces may belong to a PREVIOUS step's
        background commit (async saves report at the next manager call), so
        the sweep drains the async manager first — after which no healthy
        save can be in flight — and then clears EVERY stale staging dir, not
        just the current step's."""
        import shutil

        try:
            try:
                self._mgr.wait_until_finished()
            except Exception:  # noqa: BLE001 — the failure is already being
                pass  # handled by the retry loop; the drain is for safety
            # the directory property keeps epath for gs://-style stores —
            # a plain Path() wrap would mangle the scheme and turn the
            # remote sweep into a silent no-op
            root = self.directory
            if not root.exists():
                return
            for p in root.glob("*.orbax-checkpoint-tmp-*"):
                if isinstance(p, Path):
                    shutil.rmtree(p, ignore_errors=True)
                else:
                    try:
                        p.rmtree()  # epath: remote store
                    except Exception:  # noqa: BLE001 — best-effort sweep
                        pass
            # an interrupted save can leave the manager believing the step
            # exists; drop it from the registry so the retry can re-save it
            try:
                if step in (self._mgr.all_steps() or []):
                    final = root / str(step)
                    if not final.exists():
                        self._mgr.reload()
            except Exception:  # noqa: BLE001 — registry probe is best-effort
                pass
        except Exception as e:  # noqa: BLE001 — cleanup must never mask the save error
            logger.warning("partial-save cleanup at step %d failed: %s", step, e)

    def wait(self) -> None:
        """Block until any in-flight async save commits.  With the
        post-commit audit on, the freshly committed steps are handed to the
        background auditor here and any finished verdict is applied — still
        without ever blocking on an audit in flight."""
        self._mgr.wait_until_finished()
        if self._auditor is not None:
            self._kick_audits()
            self._apply_audit_verdicts()

    # -- restore ------------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def verify_step(self, step: int) -> "ck_integrity.StepVerification":
        """Template-free integrity verification of one retained step
        (:func:`checkpoint.integrity.verify_step` over this manager)."""
        return ck_integrity.verify_step(self.directory, step, mgr=self._mgr)

    def verified_latest_step(
        self, *, quarantine: Optional[bool] = None
    ) -> Optional[int]:
        """The newest retained step that passes integrity verification,
        walking BACK through the retention chain past corrupt steps (each
        quarantined: renamed out of the discovery namespace + ledger entry,
        so restore, elastic replan, and every later discovery agree on the
        same step).  ``None`` when no checkpoint exists at all; raises
        :class:`CheckpointIntegrityError` with the per-step verdicts when
        steps exist but NONE verifies.

        A step without a sidecar (pre-integrity checkpoint) verifies as
        ``legacy`` — restorable with a warning, never a crash."""
        icfg = self.config.integrity
        quarantine = icfg.quarantine if quarantine is None else quarantine
        steps = sorted(self._mgr.all_steps() or [], reverse=True)
        if not steps:
            return None
        trail = self._trail()
        verdicts: list[ck_integrity.StepVerification] = []
        walked = 0
        for step in steps:
            v = self.verify_step(step)
            verdicts.append(v)
            trail["verify_seconds"] = round(
                trail["verify_seconds"] + v.seconds, 3)
            if v.status == "gone":
                # the dir vanished between the step listing and the read
                # (concurrent quarantine/retention on another actor):
                # nothing to restore OR quarantine — keep walking
                logger.warning(
                    "checkpoint step %d vanished mid-verification — "
                    "skipping (concurrent retention/quarantine?)", step)
                continue
            if v.passed:
                if v.status == "legacy":
                    logger.warning(
                        "checkpoint step %d predates integrity sidecars — "
                        "restoring UNVERIFIED (legacy checkpoint; the next "
                        "save will carry digests)", step)
                    trail["legacy_restore"] = True
                if walked:
                    logger.warning(
                        "integrity walk-back: restored step is %d, %d newer "
                        "step(s) quarantined as corrupt", step, walked)
                trail["verified_step"] = int(step)
                trail["walk_back_count"] = walked
                return int(step)
            walked += 1
            if quarantine:
                ck_integrity.apply_quarantine(
                    self.directory, step, reason=v.failures[0] if v.failures
                    else "digest-mismatch", failures=v.failures)
                self._mgr.reload()
                if step not in trail["quarantined_steps"]:
                    trail["quarantined_steps"].append(int(step))
            else:
                # walked past but deliberately NOT renamed/ledgered
                # (quarantine: false, or a warm start in someone else's run
                # dir) — the trail must not claim a quarantine that never
                # happened
                trail.setdefault("corrupt_steps_unquarantined", [])
                if step not in trail["corrupt_steps_unquarantined"]:
                    trail["corrupt_steps_unquarantined"].append(int(step))
        if all(v.status == "gone" for v in verdicts):
            # every listed step vanished under us: nothing to restore
            return None
        detail = "; ".join(
            f"step {v.step}: {v.failures[0] if v.failures else v.status}"
            for v in verdicts)
        raise CheckpointIntegrityError(
            f"every retained checkpoint under {self.directory} failed "
            f"integrity verification ({detail}) — auto-resume cannot "
            f"proceed; restore from an older backup or relaunch fresh "
            f"(quarantined step dirs keep the evidence, see "
            f"{ck_integrity.LEDGER_NAME})", verdicts)

    def read_manifest(self, step: Optional[int] = None) -> Optional[dict]:
        """The topology/plan manifest saved alongside ``step`` (newest when
        ``None``), or ``None`` when the checkpoint predates manifests (or no
        checkpoint exists).  Template-free: safe to call before any model
        state exists — the restart-time replanner's first read."""
        import orbax.checkpoint as ocp

        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        try:
            out = self._mgr.restore(
                step, args=ocp.args.Composite(manifest=ocp.args.JsonRestore())
            )["manifest"]
            return dict(out) if out is not None else None
        except Exception as e:  # noqa: BLE001 — pre-elastic checkpoints have
            # no manifest item, but a CORRUPT manifest or a transient remote
            # read error must be distinguishable in the logs: a silent None
            # here means "no replan", and the run would restore onto a stale
            # declared mesh with an opaque shape crash
            logger.warning(
                "manifest read at step %s failed (%s: %s) — treating as "
                "no-manifest; a pre-elastic checkpoint is expected here, "
                "anything else deserves a look", step, type(e).__name__, e)
            return None

    def restore(
        self,
        params_template: Any,
        opt_template: Any,
        *,
        step: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        param_specs: Any = None,
        opt_specs: Any = None,
        verify: Optional[bool] = None,
    ) -> TrainState:
        """Restore the newest (or given) step.  Templates are live pytrees or
        ShapeDtypeStructs; pass mesh+specs to restore direct-to-sharded.

        ``verify`` (default: the ``exp_manager.checkpoint.integrity`` knobs)
        — verify the integrity sidecar BEFORE imposing the mesh: newest-step
        restores walk back past corrupt steps (:meth:`verified_latest_step`);
        an explicitly requested corrupt ``step`` raises
        :class:`CheckpointIntegrityError` instead of restoring bad bytes."""
        import orbax.checkpoint as ocp

        icfg = self.config.integrity
        do_verify = (icfg.enabled and icfg.verify_restore
                     if verify is None else bool(verify))
        if step is None:
            step = (self.verified_latest_step() if do_verify
                    else self.latest_step())
        elif do_verify:
            v = self.verify_step(step)
            if not v.passed:
                raise CheckpointIntegrityError(
                    f"checkpoint step {step} under {self.directory} failed "
                    f"integrity verification: "
                    f"{'; '.join(v.failures[:4]) or v.status}", [v])
            if v.status == "legacy":
                logger.warning(
                    "checkpoint step %d predates integrity sidecars — "
                    "restoring UNVERIFIED (legacy checkpoint)", step)
                self._trail()["legacy_restore"] = True
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        # meta first: the save-time knobs (save_bf16, master dropped) change
        # what templates must look like
        meta = dict(self._mgr.restore(
            step, args=ocp.args.Composite(meta=ocp.args.JsonRestore())
        )["meta"])
        saved_bf16 = bool(meta.pop("save_bf16", False))
        master_in = bool(meta.pop("master_in_ckpt", True))
        if mesh is not None and param_specs is not None:
            p_abs = _abstract_like(params_template, param_specs, mesh)
            o_abs = _abstract_like(opt_template, opt_specs, mesh)
        else:
            p_abs = _abstract_from_tree(params_template)
            o_abs = _abstract_from_tree(opt_template)
        p_abs_read = _bf16_read_templates(p_abs) if saved_bf16 else p_abs
        master_abs = None
        if not master_in and isinstance(o_abs, dict) and "master" in o_abs:
            master_abs = o_abs["master"]
            o_abs = {k: v for k, v in o_abs.items() if k != "master"}
        restored = self._mgr.restore(
            step,
            args=ocp.args.Composite(
                params=ocp.args.StandardRestore(p_abs_read),
                opt_state=ocp.args.StandardRestore(o_abs),
            ),
        )
        params = restored["params"]
        if saved_bf16:
            # cast back up to the template dtype (resume continues in the
            # run's own precision regime; bf16 rounding is the knob's cost)
            params = _cast_like(params, p_abs)
        opt_state = dict(restored["opt_state"])
        if master_abs is not None:
            # master dropped at save time: re-seed fp32 master from the saved
            # weights (the reference's use_master_weights_in_ckpt=False path)
            opt_state["master"] = _cast_like(params, master_abs)
        return TrainState(
            params=params,
            opt_state=opt_state,
            step=int(meta.pop("step")),
            consumed_samples=int(meta.pop("consumed_samples")),
            extra=meta,
        )

    def restore_params_only(
        self,
        params_template: Any,
        *,
        step: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        param_specs: Any = None,
        verify: Optional[bool] = None,
    ) -> Any:
        """The reference's ``weight_init_only`` warm start
        (``nlp_overrides.py:565-568``): weights without optimizer/loop state.

        Integrity verification applies here too, but WITHOUT quarantine by
        default — the warm-start source is usually someone else's run dir
        (or a converter's output, which has no sidecar and restores as
        legacy); renaming steps there is not this run's call."""
        import orbax.checkpoint as ocp

        icfg = self.config.integrity
        do_verify = (icfg.enabled and icfg.verify_restore
                     if verify is None else bool(verify))
        if step is None and do_verify:
            step = self.verified_latest_step(quarantine=False)
        elif step is not None and do_verify:
            v = self.verify_step(step)
            if not v.passed:
                raise CheckpointIntegrityError(
                    f"warm-start checkpoint step {step} under "
                    f"{self.directory} failed integrity verification: "
                    f"{'; '.join(v.failures[:4]) or v.status}", [v])
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        if mesh is not None and param_specs is not None:
            p_abs = _abstract_like(params_template, param_specs, mesh)
        else:
            p_abs = _abstract_from_tree(params_template)
        saved_bf16 = False
        try:
            m = self._mgr.restore(
                step, args=ocp.args.Composite(meta=ocp.args.JsonRestore())
            )["meta"]
            saved_bf16 = bool((m or {}).get("save_bf16", False))
        except Exception:
            pass  # converter-written checkpoints carry no meta item
        p_abs_read = _bf16_read_templates(p_abs) if saved_bf16 else p_abs
        restored = self._mgr.restore(
            step, args=ocp.args.Composite(params=ocp.args.StandardRestore(p_abs_read))
        )
        params = restored["params"]
        if saved_bf16:
            params = _cast_like(params, p_abs)
        return params

    def close(self) -> None:
        if self._auditor is not None:
            # the teardown drain is DEADLINE-BOUNDED (integrity.
            # audit_deadline_seconds): a hung store read on the audit thread
            # must not wedge process exit — unfinished audits are counted
            # ``incomplete`` in the trail instead
            try:
                self._mgr.wait_until_finished()
                self._kick_audits()
                self._auditor.drain(
                    self.config.integrity.audit_deadline_seconds)
                self._apply_audit_verdicts()
            except Exception as e:  # noqa: BLE001 — teardown must finish
                logger.warning("save-audit teardown drain failed: %s", e)
            self._auditor.close(timeout=0)
        self._mgr.close()

    def __enter__(self) -> "Checkpointer":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.wait()
        self.close()
