"""Experiment management: log dirs, TensorBoard, throughput, resume detection.

Re-design of the reference's ``utils/exp_manager.py`` (579 LoC of NeMo
exp-manager glue): log-dir/version management (``exp_manager.py:81-200``),
TensorBoard logger creation (``:271-291``), step timing (``TimingCallback``,
``:64-78``), and auto-resume discovery (``check_resume``, ``:333-404``) —
without Lightning callbacks: the trainer calls ``log_metrics`` directly and
Orbax ``latest_step`` replaces newest-``*.ckpt`` scanning.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import time
from pathlib import Path
from typing import Any, Optional

from neuronx_distributed_training_tpu.telemetry import TelemetryConfig
from neuronx_distributed_training_tpu.telemetry.spans import timed_import
from neuronx_distributed_training_tpu.utils.io import atomic_write_json
from neuronx_distributed_training_tpu.utils.perf import Throughput, mfu as _mfu

logger = logging.getLogger(__name__)


def _exp_base_path(exp_dir, name):
    """``<exp-root>/<name>`` with remote-store URIs (``gs://`` etc.) routed
    through epath — ``Path()`` would mangle the scheme into a local dir
    literally named ``gs:``."""
    if "://" in str(exp_dir):
        from etils import epath

        return epath.Path(str(exp_dir)) / str(name)
    return Path(str(exp_dir)) / str(name)


def exp_root_and_name(cfg: dict) -> tuple:
    """``(exp-root, name)`` for a config — THE key-fallback chain
    (``explicit_log_dir`` → ``exp_dir`` → default, ``name`` from the block or
    the config root), shared by :meth:`ExpManager.from_config`, the elastic
    replanner's checkpoint discovery (``trainer/elastic.py``), and the drill
    harness (``tools/elastic_drill.py``) so all of them resolve the directory
    ``ExpManager`` will actually open."""
    em = dict(cfg.get("exp_manager", {}) or {})
    return (
        em.get("explicit_log_dir") or em.get("exp_dir") or "nxdt_experiments",
        em.get("name", cfg.get("name", "default")),
    )


def experiment_base_dir(cfg: dict) -> Any:
    """``<exp-root>/<name>`` for a config (see :func:`exp_root_and_name`)."""
    return _exp_base_path(*exp_root_and_name(cfg))


def latest_version(base) -> Optional[int]:
    """Newest ``version_N`` index under ``base`` (digit-suffixed dirs only,
    an operator's ``version_backup_2`` is ignored) — THE version-dir parse,
    shared by :class:`ExpManager`, the elastic replanner's checkpoint
    discovery (``trainer/elastic.py``), and the drill harness
    (``tools/elastic_drill.py``), so all three always select the same
    directory.  ``None`` when no versions exist."""
    if not base.exists():
        return None
    versions = sorted(
        int(p.name.split("_")[1])
        for p in base.glob("version_*")
        if p.name.split("_")[1].isdigit()
    )
    return versions[-1] if versions else None


class ExpManager:
    """Owns the experiment directory and metric writers."""

    def __init__(
        self,
        exp_dir: str | Path = "nxdt_experiments",
        name: str = "default",
        *,
        version: Optional[str] = None,
        create_tensorboard_logger: bool = True,
        log_every_n_steps: int = 10,
        global_batch_size: int = 1,
        resume_if_exists: bool = False,
        profile_start_step: int = 0,  # 0 = profiling off
        profile_num_steps: int = 3,
        create_wandb_logger: bool = False,
        wandb_kwargs: Optional[dict] = None,
        create_mlflow_logger: bool = False,
        mlflow_kwargs: Optional[dict] = None,
        log_files: bool = True,
        log_local_rank_0_only: bool = False,
        log_global_rank_0_only: bool = False,
        seq_len: int = 0,
        telemetry: Optional[TelemetryConfig] = None,
    ):
        base = _exp_base_path(exp_dir, name)
        if version is None:
            if resume_if_exists and base.exists():
                v = latest_version(base)
                version = f"version_{v}" if v is not None else "version_0"
            else:
                n = 0
                while (base / f"version_{n}").exists():
                    n += 1
                version = f"version_{n}"
        self.log_dir = base / version
        self.log_dir.mkdir(parents=True, exist_ok=True)
        self.checkpoint_dir = self.log_dir / "checkpoints"
        self.log_every_n_steps = log_every_n_steps
        self.throughput = Throughput(global_batch_size, seq_len=seq_len)
        self.telemetry = telemetry if telemetry is not None else TelemetryConfig()
        self._last_tput: Optional[float] = None
        self._last_step_time: Optional[float] = None
        self._metrics_file = self.log_dir / "metrics.jsonl"
        # structured tensorstats records (histogram vectors — NOT scalars)
        # stream here, next to metrics.jsonl; see log_tensorstats
        self._tensorstats_file = self.log_dir / "tensorstats.jsonl"
        #: newest decoded tensorstats record — the loop teardown persists it
        #: as the run_summary.json "tensorstats" section
        self.last_tensorstats: Optional[dict] = None
        self._run_summary_file = self.log_dir / "run_summary.json"
        # run_summary.json is a read-modify-write merge reached from the main
        # thread (census, goodput teardown) AND, when the hang watchdog fires
        # without aborting, from its timer thread (anomaly trail) — serialize
        import threading

        self._summary_lock = threading.Lock()
        # set by set_mfu_reference: (train-step FLOPs/token, chips, peak TF/s)
        self._mfu_ref: Optional[tuple[float, int, float]] = None
        # metric keys already warned about as non-scalar (warn ONCE per key:
        # the sinks take scalars only, and silently dropping a value hides
        # an instrumentation bug — but warning every boundary is log spam)
        self._warned_nonscalar: set[str] = set()

        # windowed device-time capture (telemetry.trace): summary lands in
        # trace_summary.json next to run_summary.json.  The reference's
        # profile_start_step / profile_num_steps is an alias for the same
        # window with the raw artifacts kept (<log_dir>/trace, what the
        # profile plugin opens); jax allows one profiler session, so with
        # both set there is still one: telemetry.trace's, the alias skipped
        self._trace: Optional[Any] = None
        trace_cfg = self.telemetry.trace
        if profile_start_step:
            if trace_cfg.enabled:
                logger.warning(
                    "exp_manager.profile_start_step=%d skipped: "
                    "exp_manager.telemetry.trace already holds the run's one "
                    "profiler window", profile_start_step)
            else:
                from neuronx_distributed_training_tpu.telemetry.trace import (
                    TraceConfig,
                )

                trace_cfg = TraceConfig(
                    enabled=True, start_step=int(profile_start_step),
                    num_steps=int(profile_num_steps), keep_raw=True)
        if trace_cfg.enabled:
            from neuronx_distributed_training_tpu.telemetry.trace import (
                TraceCapture,
            )

            self._trace = TraceCapture(trace_cfg, self.log_dir)

        self._tb = None
        if create_tensorboard_logger:
            try:
                self._tb = _ScalarEvents(self.log_dir / "tb")
            except Exception as e:  # noqa: BLE001 — TB is optional observability
                logger.warning("TensorBoard logger unavailable: %s", e)
        self._wandb = None
        if create_wandb_logger:
            try:
                import wandb

                self._wandb = wandb.init(
                    dir=str(self.log_dir), name=name, **(wandb_kwargs or {})
                )
            except Exception as e:  # noqa: BLE001 — W&B is optional
                logger.warning("W&B logger unavailable: %s", e)
        self._mlflow = None
        if create_mlflow_logger:
            # reference create_mlflow_logger/mlflow_logger_kwargs
            # (utils/exp_manager.py:133-135, 223-228); soft-gated import
            try:
                import mlflow

                kw = dict(mlflow_kwargs or {})
                mlflow.set_tracking_uri(
                    kw.pop("tracking_uri", f"file:{self.log_dir / 'mlruns'}")
                )
                mlflow.set_experiment(kw.pop("experiment_name", name))
                self._mlflow = mlflow
                self._mlflow_run = mlflow.start_run(run_name=version)
            except Exception as e:  # noqa: BLE001 — MLflow is optional
                logger.warning("MLflow logger unavailable: %s", e)
        self._file_handler = None
        if log_files:
            self._file_handler = self._setup_rank_log_file(
                log_local_rank_0_only, log_global_rank_0_only
            )

    def _setup_rank_log_file(self, local_rank_0_only: bool,
                             global_rank_0_only: bool):
        """Per-rank log files (reference ``exp_manager.py:249-268``:
        ``nemo_log_globalrank-G_localrank-L.txt`` with rank-0-only gating)."""
        if local_rank_0_only and global_rank_0_only:
            raise ValueError(
                "Cannot set both log_local_rank_0_only and "
                "log_global_rank_0_only; pick one or neither."
            )
        import jax

        g = jax.process_index()
        # one process per host on TPU: local rank == 0 within its host
        local = 0
        if (global_rank_0_only and g != 0) or (local_rank_0_only and local != 0):
            return None
        # SLURM relaunches write under restart_N/ so earlier logs survive
        # (reference train_setup.sh:28-29 restart-count log pathing); the
        # version dir itself is shared so checkpoint auto-resume still works
        from pathlib import Path

        from neuronx_distributed_training_tpu.utils.launch import restart_log_dir

        log_dir = Path(restart_log_dir(str(self.log_dir)))
        log_dir.mkdir(parents=True, exist_ok=True)
        path = log_dir / f"nxdt_log_globalrank-{g}_localrank-{local}.txt"
        handler = logging.FileHandler(path)
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s [%(name)s] %(message)s"
        ))
        logging.getLogger().addHandler(handler)
        return handler

    @classmethod
    def from_config(cls, cfg: dict[str, Any], global_batch_size: int = 1) -> "ExpManager":
        """Build from the reference's ``exp_manager:`` block
        (``config_overview.rst:200-249``)."""
        em = dict(cfg.get("exp_manager", {}) or {})
        exp_dir, name = exp_root_and_name(cfg)
        return cls(
            exp_dir=exp_dir,
            name=name,
            create_tensorboard_logger=bool(em.get("create_tensorboard_logger", True)),
            log_every_n_steps=int(
                (cfg.get("trainer", {}) or {}).get("log_every_n_steps", 10)
            ),
            global_batch_size=global_batch_size,
            resume_if_exists=bool(em.get("resume_if_exists", False)),
            profile_start_step=int(em.get("profile_start_step", 0) or 0),
            profile_num_steps=int(em.get("profile_num_steps", 3)),
            create_wandb_logger=bool(em.get("create_wandb_logger", False)),
            wandb_kwargs=dict(em.get("wandb_logger_kwargs", {}) or {}),
            create_mlflow_logger=bool(em.get("create_mlflow_logger", False)),
            mlflow_kwargs=dict(em.get("mlflow_logger_kwargs", {}) or {}),
            log_files=bool(em.get("log_files", True)),
            log_local_rank_0_only=bool(em.get("log_local_rank_0_only", False)),
            log_global_rank_0_only=bool(em.get("log_global_rank_0_only", False)),
            seq_len=int((cfg.get("data", {}) or {}).get("seq_length", 0) or 0),
            telemetry=TelemetryConfig.from_config(em.get("telemetry")),
        )

    # -- profiling (jax.profiler -> TensorBoard profile plugin; the TPU-native
    # replacement for neuron-top/neuron-monitor, SURVEY.md §5.1) --------------

    def set_pipeline_facts(self, facts: Optional[dict[str, Any]]) -> None:
        """Arm the trace capture's pipeline-timeline reconstruction with the
        resolved schedule facts (``telemetry.step_timeline.pipeline_facts``).
        The trainer calls this once the schedule is known; with pp > 1 the
        next closed trace window carries the ``"pipeline"`` section and
        ``bubble_fraction_measured`` lands in ``run_summary.json`` next to
        the predicted fraction."""
        if self._trace is not None:
            self._trace.pipeline = dict(facts) if facts else None

    def set_comms_facts(self, facts: Optional[dict[str, Any]]) -> None:
        """Arm the trace capture's interconnect join with the cost model's
        per-axis byte volumes and the topology peak
        (``telemetry.comms.comms_section`` inputs).  The trainer calls this
        once the plan resolves; the next closed trace window then joins the
        MEASURED per-class wire seconds with the priced byte volumes into
        a ``"comms"`` section — per-class achieved_gbps and efficiency —
        in ``trace_summary.json`` / ``run_summary.json`` and through the
        metric sinks as ``comms/*`` scalars."""
        if self._trace is not None:
            self._trace.comms = dict(facts) if facts else None

    def maybe_trace(self, step: int) -> None:
        """Advance the ``telemetry.trace`` capture window (no-op when the
        knob is off).  When the window closes, the analyzed summary is in
        ``trace_summary.json`` and its headline numbers (achieved overlap,
        exposed collective seconds) are merged into ``run_summary.json``."""
        if self._trace is None:
            return
        summary = self._trace.maybe_update(step)
        if summary is not None:
            self._record_trace_summary(summary)

    @property
    def trace_active(self) -> bool:
        """Is a telemetry.trace capture window currently open?  The trainer
        keeps emitting ``StepTraceAnnotation``s while this is True even when
        ``spans`` is off, so per-step attribution always has windows."""
        return self._trace is not None and self._trace.active

    def _record_trace_summary(self, summary: dict[str, Any]) -> None:
        section: dict[str, Any] = {"trace": {
            "achieved_overlap": summary.get("achieved_overlap"),
            "exposed_collective_seconds": summary.get(
                "exposed_collective_seconds"),
            "collective_seconds": summary.get("collective_seconds"),
            "window": summary.get("window"),
            "summary_path": str(self._trace.summary_path),
        }}
        pipe = summary.get("pipeline")
        if isinstance(pipe, dict):
            # the MEASURED bubble fraction is a run fact: it lives at the
            # top level of run_summary.json beside bubble_fraction_predicted
            # (the compile-census run fact), plus a compact pipeline block
            section["bubble_fraction_measured"] = pipe.get(
                "bubble_fraction_measured")
            section["trace"]["pipeline"] = {
                k: pipe.get(k)
                for k in ("schedule", "bubble_fraction_measured",
                          "bubble_fraction_predicted", "bubble_residual",
                          "straggler_stage", "lane_resolution", "num_lanes")
                if pipe.get(k) is not None
            }
        comms = summary.get("comms")
        if isinstance(comms, dict):
            # the achieved-bandwidth join is a run fact too: per-class
            # achieved_gbps/efficiency at the top level, and comms/* scalars
            # through every sink (and the fleet beacon's metric pick)
            section["comms"] = comms
            try:
                from neuronx_distributed_training_tpu.telemetry.comms import (
                    comms_metrics,
                )

                scalars = comms_metrics(comms)
                if scalars:
                    window = summary.get("window") or {}
                    step = int(window.get("start_step", 0) or 0) + int(
                        window.get("num_steps", 0) or 0)
                    self.log_metrics(step, scalars, force=True)
            except Exception as e:  # noqa: BLE001 — telemetry only
                logger.warning("comms metric emission failed: %s", e)
        self.write_run_summary(section)

    # -- per-step hooks -----------------------------------------------------

    def step_timed(self, num_steps: int = 1, exclude_seconds: float = 0.0) -> float:
        """Record a step boundary covering ``num_steps`` steps since the last
        call; returns per-step wall seconds (0.0 on first).

        ``exclude_seconds`` — wall time since the last call spent OUTSIDE
        steady-state training (validation, checkpointing, first-step compile;
        the trainer passes ``SpanTimer.take_excluded()``) — is subtracted
        before the per-step division, so the throughput window and
        ``throughput_peak`` reflect training only instead of silently folding
        a checkpoint stall into seq/s."""
        now = time.perf_counter()
        if self._last_step_time is None:
            dt = 0.0
        else:
            window = now - self._last_step_time - max(exclude_seconds, 0.0)
            dt = max(window, 0.0) / max(num_steps, 1)
        self._last_step_time = now
        if dt > 0:
            self._last_tput = self.throughput.update(dt, num_steps=num_steps)
        return dt

    def set_mfu_reference(
        self,
        *,
        train_step_flops_per_token: float,
        n_chips: int,
        peak_tflops_per_chip: float,
    ) -> None:
        """Arm MFU/tokens-per-sec-per-chip logging.  The trainer calls this
        once with the analytic per-family FLOPs estimate
        (``models.family.flops_for_model`` x3 for fwd+2xbwd); from then on every
        ``log_metrics`` derives ``mfu`` from the throughput window's
        ``tokens_per_sec`` — one source of truth, no second timer.  A peak of
        0 (off the TPU) logs tokens/s/chip and no ``mfu``."""
        self._mfu_ref = (
            float(train_step_flops_per_token), max(int(n_chips), 1),
            float(peak_tflops_per_chip),
        )

    def write_run_summary(self, section: dict[str, Any]) -> None:
        """Merge ``section`` into ``run_summary.json`` (next to
        ``metrics.jsonl``): the one-shot facts of the run — compile census,
        goodput totals — that don't belong in the per-step stream.

        The write is atomic (serialize, temp file, rename): a SIGKILL
        mid-write — preemption, OOM-killer, the elastic drill's kill
        injector — must never leave a truncated document for resume or
        reporting to choke on, and an unserializable ``section`` raises
        with the previous contents intact."""
        with self._summary_lock:
            existing: dict[str, Any] = {}
            try:
                with open(self._run_summary_file) as f:
                    existing = json.load(f)
            except (OSError, ValueError):
                pass
            existing.update(section)
            atomic_write_json(self._run_summary_file, existing)

    def log_metrics(self, step: int, metrics: dict[str, Any], *, force: bool = False) -> None:
        """Write scalars (TB + jsonl) every ``log_every_n_steps``.

        Scalars logged mirror the reference's set: reduced_train_loss, lr,
        grad/param norm, throughput, throughput_peak, consumed_samples
        (``base.py:624-654``).  Non-scalar values are coerced when they hold
        exactly one element (0-d / size-1 arrays) and otherwise dropped with
        a once-per-key warning naming the offender — every sink (TB, W&B,
        MLflow, jsonl) takes scalars only, and a silent drop hides the
        instrumentation bug that produced the value."""
        if not force and step % self.log_every_n_steps != 0:
            return
        flat: dict[str, float] = {}
        stray_tensorstats: dict[str, Any] = {}
        for k, v in metrics.items():
            f = _coerce_scalar(v)
            if f is None:
                if k.startswith("tensorstats"):
                    # a tensorstats histogram vector that reached the scalar
                    # path (a caller that didn't pre-split the boundary
                    # fetch): route it to its own stream instead of the
                    # warn-once drop — the payload is structured BY DESIGN
                    stray_tensorstats[k] = v
                    continue
                if k not in self._warned_nonscalar:
                    self._warned_nonscalar.add(k)
                    shape = getattr(v, "shape", None)
                    logger.warning(
                        "log_metrics: dropping non-scalar metric %r "
                        "(%s%s) — the TB/W&B/MLflow/jsonl sinks take "
                        "scalars; log a reduction instead (warned once)",
                        k, type(v).__name__,
                        f", shape {tuple(shape)}" if shape is not None
                        else "",
                    )
                continue
            flat[k] = f
        if stray_tensorstats:
            self.log_tensorstats(step, stray_tensorstats)
        if self._last_tput is not None:
            flat["throughput_seqs_per_sec"] = self._last_tput
            flat["throughput_peak"] = self.throughput.peak
            tokens = self.throughput.tokens_per_sec
            if self.telemetry.mfu and self._mfu_ref is not None and tokens > 0:
                step_flops, n_chips, peak_tf = self._mfu_ref
                per_chip = tokens / n_chips
                flat["tokens_per_sec_per_chip"] = per_chip
                if peak_tf > 0:
                    flat["mfu"] = _mfu(per_chip, step_flops, peak_tf)
        if self._tb is not None:
            self._tb.add_scalars(step, flat)
        if self._wandb is not None:
            self._wandb.log(flat, step=step)
        if self._mlflow is not None:
            self._mlflow.log_metrics(flat, step=step)
        with open(self._metrics_file, "a") as f:
            f.write(json.dumps({"step": step, **flat}) + "\n")

    def log_tensorstats(self, step: int, payload: dict[str, Any]) -> None:
        """Append one structured tensor-numerics-observatory record to
        ``tensorstats.jsonl``.

        ``payload`` maps ``tensorstats_hist/<phase>/<group>`` metric keys to
        the packed cumulative vectors fetched at the boundary (numpy arrays
        or float sequences — see ``telemetry.tensorstats.CUM_HEADER``).
        These are ARRAYS: they must never reach the scalar sinks, so they
        get their own strict-JSON stream (one decoded record per boundary)
        plus ``self.last_tensorstats`` for the run_summary teardown
        section.  Keys without the hist prefix are ignored (defensive: the
        caller may hand over a mixed dict)."""
        from neuronx_distributed_training_tpu.telemetry.tensorstats import (
            HIST_PREFIX,
            decode_cum,
        )

        cfg = self.telemetry.tensorstats
        groups: dict[str, Any] = {}
        for k, v in payload.items():
            if not k.startswith(HIST_PREFIX):
                continue
            try:
                groups[k[len(HIST_PREFIX):]] = decode_cum(v, cfg)
            except (TypeError, ValueError) as e:
                logger.warning(
                    "log_tensorstats: undecodable payload for %r: %s", k, e)
        if not groups:
            return
        rec = {
            "step": int(step),
            "hist_lo_exp": cfg.hist_lo_exp,
            "hist_hi_exp": cfg.hist_hi_exp,
            "groups": groups,
        }
        self.last_tensorstats = rec
        try:
            with open(self._tensorstats_file, "a") as f:
                f.write(json.dumps(rec, allow_nan=False) + "\n")
        except (OSError, ValueError, TypeError) as e:
            # observability must not kill training
            logger.warning("tensorstats.jsonl write failed: %s", e)

    def close(self) -> None:
        if self._trace is not None:
            summary = self._trace.close()
            if summary is not None:
                self._record_trace_summary(summary)
        if self._tb is not None:
            self._tb.close()
            self._tb = None
        if self._wandb is not None:
            self._wandb.finish()
        if self._mlflow is not None:
            self._mlflow.end_run()
        if self._file_handler is not None:
            logging.getLogger().removeHandler(self._file_handler)
            self._file_handler.close()
            self._file_handler = None


class _ScalarEvents:
    """The TensorBoard sink: a boundary's scalars as one ``Event`` of a
    ``tfevents`` file under ``logdir``, each a ``simple_value`` under its
    metric key, on tensorboard's own queue-and-thread writer (a queue of 10,
    a flush every 120 s).  It loads neither ``torch`` nor ``tensorflow``,
    whose import was 21-23 s of a start-up (``PERF.md`` section 6, PR 48)."""

    def __init__(self, logdir: Path):
        with timed_import("tensorboard.summary.writer.event_file_writer"):
            from tensorboard.compat.proto import event_pb2, summary_pb2
            from tensorboard.summary.writer import event_file_writer as efw
            from tensorboard.summary.writer.record_writer import RecordWriter

        self._event, self._summary = event_pb2.Event, summary_pb2.Summary
        logdir.mkdir(parents=True, exist_ok=True)
        # ``EventFileWriter`` itself opens its file through
        # ``tensorboard.compat.tf``, which imports tensorflow where it is
        # installed (9 s): its file name and its parts over a plain file
        name = "events.out.tfevents.%010d.%s.%s.%s" % (
            time.time(), socket.gethostname(), os.getpid(),
            efw._global_uid.get())
        self._writer = efw._AsyncWriter(
            RecordWriter(open(logdir / name, "wb")),
            max_queue_size=10, flush_secs=120)
        self._add(self._event(
            file_version="brain.Event:2",
            source_metadata=event_pb2.SourceMetadata(writer=__name__)))
        self._writer.flush()

    def _add(self, event: Any) -> None:
        event.wall_time = time.time()
        self._writer.write(event.SerializeToString())

    def add_scalars(self, step: int, scalars: dict[str, float]) -> None:
        value = self._summary.Value
        self._add(self._event(step=int(step), summary=self._summary(value=[
            value(tag=k, simple_value=v) for k, v in scalars.items()])))

    def close(self) -> None:
        """Write what is queued, then close the file."""
        self._writer.close()


def _is_scalar(v: Any) -> bool:
    return _coerce_scalar(v) is not None


def _coerce_scalar(v: Any) -> Optional[float]:
    """Host float from a scalar-like value, else None.

    ``float()`` covers Python numbers and numpy/jax 0-d arrays / device
    scalars; size-1 arrays of higher rank (``np.array([3.0])``) go through
    ``item()`` (newer numpy deprecates ``float()`` on them).  Multi-element
    arrays (and anything else) return None — the caller decides whether to
    warn."""
    if getattr(v, "ndim", 0):
        if getattr(v, "size", 0) == 1:
            try:
                return float(v.item())
            except (TypeError, ValueError):
                return None
        return None
    try:
        return float(v)
    except (TypeError, ValueError):
        pass
    item = getattr(v, "item", None)
    if item is not None and getattr(v, "size", 0) == 1:
        try:
            return float(item())
        except (TypeError, ValueError):
            pass
    return None
