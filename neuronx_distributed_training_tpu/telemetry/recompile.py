"""Recompile / retrace detection.

A jitted function silently retraces whenever an argument's abstract shape,
dtype, or tree structure changes — on TPU that is a multi-minute compile that
looks like a hung step, and the classic trigger is a data loader yielding a
ragged final batch.  ``RecompileDetector`` fingerprints the abstract
signature of each named function's arguments on every call (pure host-side
metadata: shapes and dtypes, never values — no device sync) and, when the
signature changes mid-run, logs a warning naming exactly which leaves changed
and how.

This detects the CAUSE (a signature change) at dispatch time rather than the
symptom (a stalled step) minutes later; when the trainer has swapped in an
AOT-compiled step, the same check turns XLA's opaque "argument mismatch"
error into a readable shape diff.

The signature check sees the host's side only.  The process's one
``jax.monitoring`` listener counts what XLA actually did, from the package's
first import on: every backend compile (or persistent-cache read, which the
same jax event wraps), the Python tracing and lowering before it (which no
cache saves), and the persistent cache's hits, misses and retrieval time,
each under the span open when it fired (``spans.open_phase``) — ``COMPILES``,
the ``compiles`` and ``compile_cache`` of ``run_summary.json``'s ``startup``
section.  ``watch_compiles`` additionally lists the backend compiles of one
``fit()`` with the trainer step they hit — its ``compile_events``.  A stall the
size of a compile inside the steady window shows there with its step.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Optional

import jax

from neuronx_distributed_training_tpu.telemetry import spans as _spans

logger = logging.getLogger(__name__)

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax event -> the whole-run total it feeds (seconds; counts)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _BACKEND_COMPILE_EVENT: "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    # recorded when a compile is WRITTEN to the cache: one that took the
    # cache's minimum compile time (utils/compile_cache.py: 1 s) or longer
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileLog:
    """What the listener accumulates, apart from any capped list: whole-run
    totals, and per phase the backend compiles (``n``, ``seconds``) and the
    tracing and lowering (``trace_lower_s``) that fired under it."""

    def __init__(self) -> None:
        self.totals: dict[str, float] = {
            **{k: 0.0 for k in _DURATIONS.values()},
            **{k: 0 for k in _COUNTS.values()},
            # every call jax made to the two listeners: what listening costs
            "listener_calls": 0}
        self.by_phase: dict[str, dict[str, float]] = {}

    def _phase(self) -> dict[str, float]:
        return self.by_phase.setdefault(
            _spans.open_phase() or "unattributed",
            {"n": 0, "seconds": 0.0, "trace_lower_s": 0.0})

    def duration(self, event: str, seconds: float) -> None:
        self.totals["listener_calls"] += 1
        key = _DURATIONS.get(event)
        if key is None:
            return
        self.totals[key] += seconds
        if key == "backend_s":
            phase = self._phase()
            phase["n"] += 1
            phase["seconds"] += seconds
        elif key in ("trace_s", "lower_s"):
            self._phase()["trace_lower_s"] += seconds

    def count(self, event: str) -> None:
        self.totals["listener_calls"] += 1
        key = _COUNTS.get(event)
        if key is not None:
            self.totals[key] += 1

    def summary(self) -> dict:
        """``{"totals", "by_phase"}``, rounded, for ``run_summary.json``."""
        return {
            "totals": {k: round(v, 6) for k, v in self.totals.items()},
            "by_phase": {p: {k: round(v, 6) for k, v in d.items()}
                         for p, d in self.by_phase.items()},
        }


#: the process's log, from the package's first import
COMPILES = CompileLog()
# jax.monitoring has no unregister: ONE pair of listeners per process,
# registered below when the package is first imported.  They feed COMPILES
# and forward backend compiles to whichever detector the running fit()
# pointed them at.  They are called only when jax records an event (trace,
# lower, compile, cache), never on a steady step.
_compile_sink: Optional[Callable[[float, Optional[str]], None]] = None


def _on_event_duration(event: str, duration_secs: float, **_: Any) -> None:
    COMPILES.duration(event, duration_secs)
    sink = _compile_sink
    if sink is not None and event == _BACKEND_COMPILE_EVENT:
        sink(duration_secs, _spans.open_phase())


def _on_event(event: str, **_: Any) -> None:
    COMPILES.count(event)


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
jax.monitoring.register_event_listener(_on_event)


def _leaf_sig(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None:
        return f"<{type(x).__name__}>"
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def _signature(args: tuple) -> dict[str, str]:
    """{leaf path: "dtype[shape]"} over all positional args."""
    out: dict[str, str] = {}
    for i, arg in enumerate(args):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in leaves:
            key = f"arg{i}" + "".join(str(p) for p in path)
            out[key] = _leaf_sig(leaf)
    return out


class RecompileDetector:
    """Warns (once per change) when a jitted fn's abstract arg signature
    changes mid-run — the retrace-about-to-happen signal."""

    #: retained event cap — a loader alternating between two signatures fires
    #: every step; the tail is what run_summary.json reports anyway
    MAX_EVENTS = 100
    #: newest backend compiles kept (a run's first steps compile dozens of
    #: small programs; the ones that matter are the late ones)
    MAX_COMPILE_EVENTS = 50

    def __init__(self) -> None:
        self._seen: dict[str, dict[str, str]] = {}
        self._warned: set[str] = set()
        self.events: list[str] = []
        self.compile_events: list[dict[str, Any]] = []

    def watch_compiles(self, step_of: Callable[[], int]) -> None:
        """Record every backend compile from now on as ``{"step":
        step_of(), "seconds": s, "phase": <the span open, or None>}`` in
        ``compile_events`` (takes the process's listener over from any
        earlier detector)."""
        global _compile_sink

        def sink(seconds: float, phase: Optional[str]) -> None:
            self.compile_events.append(
                {"step": int(step_of()), "seconds": round(seconds, 4),
                 "phase": phase})
            del self.compile_events[:-self.MAX_COMPILE_EVENTS]

        _compile_sink = sink

    def unwatch_compiles(self) -> None:
        global _compile_sink
        _compile_sink = None

    def check(self, name: str, *args: Any) -> bool:
        """Record ``args``' signature under ``name``; returns True (and
        warns with the offending diff — once per distinct diff, so an
        alternating loader can't flood the log) when it changed since the
        last call."""
        sig = _signature(args)
        prev = self._seen.get(name)
        self._seen[name] = sig
        if prev is None or prev == sig:
            return False
        diff = self.describe_diff(prev, sig)
        event = f"{name}: {diff}"
        self.events.append(event)
        del self.events[:-self.MAX_EVENTS]
        if event not in self._warned:
            self._warned.add(event)
            logger.warning(
                "argument signature for %r changed mid-run: a jitted step now "
                "retraces (a full recompile); an AOT-compiled step will "
                "instead reject the call with an argument mismatch — %s",
                name, diff,
            )
        return True

    def signature(self, name: str) -> dict[str, str] | None:
        """The last recorded abstract signature for ``name`` — the batch
        fingerprint the numerics flight recorder ring-buffers per step (pure
        host metadata, shared with the retrace check: one source of truth)."""
        return self._seen.get(name)

    @staticmethod
    def describe_diff(prev: dict[str, str], cur: dict[str, str]) -> str:
        parts: list[str] = []
        for key in sorted(set(prev) | set(cur)):
            a, b = prev.get(key), cur.get(key)
            if a == b:
                continue
            if a is None:
                parts.append(f"{key}: added {b}")
            elif b is None:
                parts.append(f"{key}: removed (was {a})")
            else:
                parts.append(f"{key}: {a} -> {b}")
        if len(parts) > 8:
            parts = parts[:8] + [f"... and {len(parts) - 8} more"]
        return "; ".join(parts) or "tree structure changed"
