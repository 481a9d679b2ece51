"""Windowed device-time trace capture (``exp_manager.telemetry.trace``).

A programmatic ``jax.profiler`` window around a few steady-state steps: the
trainer starts the trace when the loop reaches ``start_step``, stops it
``num_steps`` later, parses the emitted artifacts into the device-time
summary (``telemetry.trace_analysis``), and writes ``trace_summary.json``
next to ``run_summary.json``.  Steps outside the window are untouched — the
capture adds no host syncs and no graph changes, so the AOT-once /
dispatch-ahead contract tests hold with the knob on or off.

.. code-block:: yaml

    exp_manager:
      telemetry:
        trace:
          enabled: false    # the windowed capture (off by default)
          start_step: 1     # first traced step (skip step 0: compile lives there)
          num_steps: 3      # window length
          keep_raw: false   # keep the raw profiler artifacts (TensorBoard's
                            # profile plugin reads them); default: delete
                            # after analysis — the summary is the product

The profiler session is process-global in jax — only one trace can be live.
``start_session``/``stop_session`` guard it with an owner token so this
capture and teardown can never double-start or double-stop
it (a ``stop_trace`` on an already-closed session raises deep in teardown
otherwise).  The reference's ``exp_manager.profile_start_step`` /
``profile_num_steps`` is an alias that builds this block with ``keep_raw``
(``ExpManager.__init__``): one window, one session.
"""

from __future__ import annotations

import dataclasses
import logging
import shutil
import threading
from pathlib import Path
from typing import Any, Mapping, Optional

logger = logging.getLogger(__name__)

# -- the process-global profiler session guard ------------------------------

_SESSION_LOCK = threading.Lock()
_SESSION_OWNER: Optional[str] = None


def start_session(log_dir: str, owner: str) -> bool:
    """Start the global ``jax.profiler`` trace for ``owner``.  Returns False
    (and logs) instead of raising when another owner already holds the
    session or the profiler refuses — observability must not kill training."""
    global _SESSION_OWNER
    with _SESSION_LOCK:
        if _SESSION_OWNER is not None:
            logger.warning(
                "profiler trace requested by %r but %r already holds the "
                "session (jax allows one); skipping this window",
                owner, _SESSION_OWNER,
            )
            return False
        import jax

        try:
            jax.profiler.start_trace(str(log_dir))
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            logger.warning("profiler start_trace failed for %r: %s", owner, e)
            return False
        _SESSION_OWNER = owner
        return True


def stop_session(owner: str) -> bool:
    """Stop the global trace IF ``owner`` holds it.  Never raises: a stop
    after the window already closed (or after an out-of-band stop) is a
    logged no-op, not a teardown crash."""
    global _SESSION_OWNER
    with _SESSION_LOCK:
        if _SESSION_OWNER != owner:
            return False
        import jax

        _SESSION_OWNER = None
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — e.g. stopped out-of-band
            logger.warning("profiler stop_trace for %r: %s", owner, e)
            return False
        return True


def session_owner() -> Optional[str]:
    with _SESSION_LOCK:
        return _SESSION_OWNER


# -- the knob block ---------------------------------------------------------


def _trace_knobs() -> set[str]:
    return {f.name for f in dataclasses.fields(TraceConfig)}


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    enabled: bool = False
    start_step: int = 1
    num_steps: int = 3
    keep_raw: bool = False

    @classmethod
    def from_config(cls, block: Any) -> "TraceConfig":
        """Parse (and validate) an ``exp_manager.telemetry.trace`` block.

        Accepts ``None`` (defaults: disabled), a bare bool (``trace: true``
        enables the default window), or a mapping of knobs.  Unknown keys
        raise with a did-you-mean hint — a typo'd window must not silently
        trace nothing.
        """
        if block is None:
            return cls()
        if isinstance(block, bool):
            return cls(enabled=block)
        knobs = _trace_knobs()
        if not isinstance(block, Mapping):
            raise ValueError(
                f"exp_manager.telemetry.trace must be a mapping of "
                f"{sorted(knobs)} (or a single bool), got "
                f"{type(block).__name__}"
            )
        unknown = set(block) - knobs
        if unknown:
            from neuronx_distributed_training_tpu.config.loader import (
                did_you_mean,
            )

            raise ValueError(
                f"unknown exp_manager.telemetry.trace keys {sorted(unknown)}; "
                f"supported: {sorted(knobs)}" + did_you_mean(unknown, knobs)
            )
        values = dict(block)
        for key in ("enabled", "keep_raw"):
            if key in values and not isinstance(values[key], bool):
                raise ValueError(
                    f"exp_manager.telemetry.trace.{key} must be a boolean, "
                    f"got {values[key]!r}"
                )
        out = cls(
            enabled=bool(values.get("enabled", cls.enabled)),
            start_step=int(values.get("start_step", cls.start_step)),
            num_steps=int(values.get("num_steps", cls.num_steps)),
            keep_raw=bool(values.get("keep_raw", cls.keep_raw)),
        )
        if out.start_step < 0:
            raise ValueError(
                f"exp_manager.telemetry.trace.start_step must be >= 0, "
                f"got {out.start_step}"
            )
        if out.num_steps < 1:
            raise ValueError(
                f"exp_manager.telemetry.trace.num_steps must be >= 1, "
                f"got {out.num_steps}"
            )
        return out


# -- the windowed capture ---------------------------------------------------


class TraceCapture:
    """Drives one capture window over the training loop's step counter.

    The trainer calls :meth:`maybe_update` once per step (before dispatch)
    and :meth:`close` at teardown; the
    window [start_step, start_step + num_steps) is traced, analyzed, and
    summarized exactly once.  Every failure degrades to a warning.
    """

    _OWNER = "telemetry.trace"

    def __init__(self, cfg: TraceConfig, out_dir: str | Path, *,
                 top_k: int = 15,
                 pipeline: Optional[Mapping[str, Any]] = None):
        self.cfg = cfg
        self.out_dir = Path(out_dir)
        self.raw_dir = self.out_dir / "trace"
        self.summary_path = self.out_dir / "trace_summary.json"
        self.top_k = top_k
        # schedule facts (telemetry.step_timeline.pipeline_facts) — the
        # trainer sets them once the schedule resolves; pp > 1 turns the
        # analyzed summary's "pipeline" section on
        self.pipeline = dict(pipeline) if pipeline else None
        # interconnect facts (telemetry.comms.comms_section inputs) — the
        # trainer sets them once the plan resolves; joining the analyzed
        # per-class wire seconds with the cost model's byte volumes turns
        # the summary's "comms" section (achieved_gbps / efficiency) on
        self.comms: Optional[dict[str, Any]] = None
        self.active = False
        self.done = False
        self.summary: Optional[dict[str, Any]] = None

    def maybe_update(self, step: int) -> Optional[dict[str, Any]]:
        """Advance the window against ``step``; returns the summary dict on
        the call that closes the window, else None."""
        if not self.cfg.enabled or self.done:
            return None
        end = self.cfg.start_step + self.cfg.num_steps
        if not self.active and self.cfg.start_step <= step < end:
            # a refused session (another owner holds the global profiler)
            # is retried at the NEXT in-window step — the window gate
            # bounds retries, and the other owner may free the session
            # mid-way through ours
            self.active = start_session(str(self.raw_dir), self._OWNER)
            return None
        if self.active and step >= end:
            return self._finish()
        if step >= end:
            self.done = True  # window passed with no session: give up
        return None

    def close(self) -> Optional[dict[str, Any]]:
        """Teardown: close a still-open window (fit() ended inside it) and
        analyze what was captured.  Safe to call repeatedly."""
        if self.active:
            return self._finish()
        return None

    def _finish(self) -> Optional[dict[str, Any]]:
        self.active = False
        self.done = True
        stop_session(self._OWNER)
        try:
            from neuronx_distributed_training_tpu.telemetry.trace_analysis import (
                analyze_trace_dir,
            )

            self.summary = analyze_trace_dir(self.raw_dir, top_k=self.top_k,
                                             pipeline=self.pipeline)
            self.summary["window"] = {
                "start_step": self.cfg.start_step,
                "num_steps": self.cfg.num_steps,
            }
            if self.comms:
                try:
                    from neuronx_distributed_training_tpu.telemetry.comms \
                        import comms_section

                    section = comms_section(
                        self.comms,
                        self.summary.get("overlap_by_class") or {},
                        window_steps=self.cfg.num_steps,
                    )
                    if section:
                        self.summary["comms"] = section
                except Exception as e:  # noqa: BLE001 — telemetry only
                    logger.warning("comms bandwidth join failed: %s", e)
            # atomic (temp + rename): a kill mid-write must not leave torn
            # JSON for the report tools to choke on
            from neuronx_distributed_training_tpu.utils.io import (
                atomic_write_json,
            )

            atomic_write_json(self.summary_path, self.summary)
            logger.info(
                "device-time trace window closed: achieved_overlap=%s "
                "exposed_collective_seconds=%s -> %s",
                self.summary.get("achieved_overlap"),
                self.summary.get("exposed_collective_seconds"),
                self.summary_path,
            )
        except Exception as e:  # noqa: BLE001 — analysis must not kill training
            logger.warning("trace analysis failed: %s", e)
            return None
        finally:
            if not self.cfg.keep_raw:
                shutil.rmtree(self.raw_dir, ignore_errors=True)
        return self.summary
