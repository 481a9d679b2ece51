"""Host-side monotonic span timing + goodput accounting.

The trainer's wall time used to be one undifferentiated ``step_time``; a slow
data loader, a checkpoint stall, and a genuine step regression all looked the
same.  ``SpanTimer`` decomposes it with named, nestable-free spans measured by
``time.perf_counter`` only — no device sync, no array access — so the loop's
dispatch-ahead contract is untouched:

- ``data_wait``  host blocked on the prefetch iterator
- ``dispatch``   enqueueing the jitted step (NOT device execution time; under
  dispatch-ahead the host returns immediately and the device runs behind)
- ``host_sync``  the boundary metric fetch (the only place device time that
  outran the host gets absorbed)
- ``compile``    first-step lower+compile (when the census runs it explicitly)
- ``log_metrics``  the metric sinks' writes after the fetch (productive; it
  runs after the boundary's drain, so it lands in the NEXT row's
  ``time/log_metrics``)
- ``validate`` / ``checkpoint`` / ``restart``  non-productive phases
- ``startup/<phase>``  the start-up timeline, process start to the end of the
  first logging boundary (``STARTUP_SPANS`` in order: ``before_program``,
  ``imports``, ``backend``, ``assemble``, ``init_params``, ``init_opt_state``,
  ``exp_manager``, ``telemetry_arming``, ``checkpointer``, ``fit_prologue``,
  all non-productive, then ``first_step``).  They are spans of ONE
  process-level timer (``STARTUP``) whose wall starts at the OS's process
  start; the first ``fit()`` of the process continues that clock
  (``SpanTimer(earlier=...)``), so the loop's spans and the phases share it and
  ``goodput``'s wall starts at process start (docs/observability.md
  "Start-up timeline")

Every span also enters a ``jax.profiler.TraceAnnotation`` of the same name, so
while a profiler window is open the spans sit on the host plane of the trace,
on the device trace's clock; with no window open that is one disabled-TraceMe
check.  ``DEVICE_SCOPES`` is the device side of the same table: the
``jax.named_scope`` names the step's layers carry into every op's metadata.

The innermost open span is also the ``phase`` a compile event is tagged with
(``open_phase``; ``telemetry/recompile.py``'s listener reads it).

Two accounting windows run in parallel: per-boundary totals (``drain`` — the
``time/<span>`` metrics) and cumulative totals since construction (goodput).
Goodput follows the usual definition: the fraction of wall time spent in
productive training (everything not in a non-productive span), the quantity
that actually predicts time-to-trained-model across restarts and evals.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Iterator, Optional

import jax

import neuronx_distributed_training_tpu as _package

#: the start-up phases before ``fit()``'s loop, in the order a run passes
#: them (docs/observability.md "Start-up timeline"); each is measured where
#: the work happens, none syncs the device
STARTUP_SPANS = tuple(f"startup/{p}" for p in (
    "before_program", "imports", "backend", "assemble", "init_params",
    "init_opt_state", "exp_manager", "telemetry_arming", "checkpointer",
    "fit_prologue"))
#: first ``dispatch`` to the end of the first boundary's ``host_sync``: a
#: training step, so productive
FIRST_STEP_SPAN = "startup/first_step"

#: spans counted against goodput AND excluded from the throughput window
#: ("replan" is the restart-time autotune re-plan on a changed world size —
#: docs/elasticity.md)
NON_PRODUCTIVE_SPANS = ("compile", "validate", "checkpoint", "restart",
                        "replan") + STARTUP_SPANS


#: ``jax.named_scope`` names inside the compiled step: top-level scope ->
#: the inner scopes it holds (docs/observability.md "Named scopes").  An op's
#: scope path reaches the profiler as the ``tf_op`` stat of its event metadata,
#: wrapped as ``jvp(<scope>)`` forward and ``transpose(jvp(<scope>))`` backward.
#: The benchmark keeps its own copy (``benchmark/readers/scope_time.py``), which
#: a test holds equal to this table; scopes that single families open besides
#: are ``FAMILY_SCOPES``.
DEVICE_SCOPES: dict[str, tuple[str, ...]] = {
    "embed": (),
    "attention": ("flash_fwd", "flash_dq", "flash_dkv"),
    "mlp": (),
    "moe": ("router", "dispatch", "experts", "combine"),
    "ce_head": (),
    "grad_accum": (),
    "optimizer": ("clip", "adamw", "zero1_bucket_ag"),
}

#: Inner scopes that one family's step opens inside a scope of
#: ``DEVICE_SCOPES``, top-level scope -> names: ``models/ouro.py``'s
#: ``ce_head/exit_gate``; ``models/laguna.py``'s ``attention/attn_full`` and
#: ``attention/attn_window`` (the whole attention block of a layer of that
#: kind, the flash kernels' scopes inside it), ``attention/.../head_gate`` and
#: ``moe/shared`` (``ops/moe.py``'s shared expert); ``models/kanana.py``'s
#: ``attention/mla_latent`` (what latent attention adds beside q, the kernels
#: and o: the down-projection, the latent's norm, the up-projection, the
#: shared key's rope, the assembly of k); ``models/lfm2.py``'s
#: ``attention/short_conv`` (the whole convolution half of a layer: norm,
#: ``in_proj``, the middle, ``out_proj``, residual), ``.../conv_gate`` inside
#: it (the middle alone: the two gates and the taps, ``ops/short_conv.py``)
#: and ``attention/qk_norm`` (the per-head norms of q and k);
#: ``models/nemotron_h.py``'s ``attention/mamba`` (a whole Mamba-2 layer: norm,
#: ``in_proj``, convolution, scan, gated norm, ``out_proj``, residual) with,
#: inside it, ``mamba_conv`` (the convolution with its bias and ``silu``),
#: ``ssd_scan`` (``ops/ssd.py``'s call, softplus and decays included) and
#: ``gated_norm`` (the gate and the grouped norm); ``models/keye.py``'s
#: ``attention/indexer`` (the learned selection's three projections, the index
#: key's LayerNorm, the rope and the index scores of every causal pair),
#: ``attention/select`` (each query's threshold and the mask of the keys it
#: keeps: ``ops/sparse_attention.py``) and ``attention/indexer_loss`` (the main
#: attention's head-mean probabilities, the KL and their backward).  A reader that does not
#: know one counts its time under the scope that holds it, so nothing becomes
#: unscoped; ``benchmark/readers/inner_scope.py`` reads one by its name.
FAMILY_SCOPES: dict[str, tuple[str, ...]] = {
    "attention": ("attn_full", "attn_window", "head_gate", "mla_latent",
                  "short_conv", "conv_gate", "qk_norm",
                  "mamba", "mamba_conv", "ssd_scan", "gated_norm",
                  "indexer", "select", "indexer_loss"),
    "moe": ("shared",),
    "ce_head": ("exit_gate",),
}


#: names of the spans open in this process, innermost last (every timer's:
#: the loop thread opens them one at a time)
_open: list[str] = []


def open_phase() -> Optional[str]:
    """The innermost open span — what a compile event that fires now is
    tagged with (``telemetry/recompile.py``)."""
    return _open[-1] if _open else None


@contextmanager
def named(name: str) -> Iterator[None]:
    """``name`` as the open phase and on the profiler's clock, untimed: for a
    caller that times itself and ``add``s (the compile census)."""
    _open.append(name)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _open.pop()


class SpanTimer:
    """Accumulates named wall-time spans; all methods are host-only.

    ``earlier`` is a timer whose clock this one continues (the process's
    start-up timer, for the first ``fit()``): the wall starts where its wall
    started and its totals count in the cumulative ones (goodput, ``snapshot``)
    without entering the per-boundary window."""

    def __init__(self, enabled: bool = True,
                 non_productive: tuple[str, ...] = NON_PRODUCTIVE_SPANS,
                 earlier: Optional["SpanTimer"] = None):
        self.enabled = enabled
        self.non_productive = frozenset(non_productive)
        self._since_drain: dict[str, float] = {}
        self._cumulative: dict[str, float] = {}
        self._excluded_since_take = 0.0
        self._t_start = time.perf_counter()
        # (name, begin, seconds) of each span while a start-up timeline is
        # open (``earlier``'s list, where there is one); None = not kept
        # (the steady loop)
        self._intervals: Optional[list[tuple[str, float, float]]] = None
        self._earlier = earlier if enabled else None
        if self._earlier is not None:
            self._t_start = earlier._t_start

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        _open.append(name)  # as ``named``, inlined: a few spans every step
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            _open.pop()
            self.add(name, time.perf_counter() - t0, begin=t0)

    def add(self, name: str, seconds: float,
            begin: Optional[float] = None) -> None:
        if not self.enabled:
            return
        self._since_drain[name] = self._since_drain.get(name, 0.0) + seconds
        self._cumulative[name] = self._cumulative.get(name, 0.0) + seconds
        if name in self.non_productive:
            self._excluded_since_take += seconds
        kept = (self._earlier or self)._intervals
        if kept is not None and begin is not None:
            kept.append((name, begin, seconds))

    def add_preexisting(self, name: str, seconds: float) -> None:
        """Account wall time spent BEFORE this timer existed (a restart-time
        replan that ran before ``fit()`` constructed the timer): the span is
        added AND the wall-clock origin moves back by the same amount, so
        ``goodput_fraction`` keeps ``nonproductive <= wall``.  A timer that
        continues an ``earlier`` one needs none of it: its wall already
        starts at process start."""
        if not self.enabled or seconds <= 0.0:
            return
        if self._earlier is None:
            self._t_start -= seconds
        self.add(name, seconds)

    # -- per-boundary window -------------------------------------------------

    def drain(self) -> dict[str, float]:
        """Span totals since the last ``drain`` (the ``time/<span>`` metrics)."""
        out, self._since_drain = self._since_drain, {}
        return out

    def snapshot(self) -> dict[str, float]:
        """Non-destructive copy of the cumulative span totals (the
        flight-recorder ring buffer stores one per step; ``drain``'s
        per-boundary window is untouched)."""
        return self._totals()

    def take_excluded(self) -> float:
        """Non-productive seconds accumulated since the last take — the wall
        time ``ExpManager.step_timed`` must subtract from its throughput
        window so checkpoint/validation/compile stalls don't contaminate
        steady-state seq/s (and ``throughput_peak`` never records a window
        that includes them)."""
        out, self._excluded_since_take = self._excluded_since_take, 0.0
        return out

    # -- cumulative (goodput) ------------------------------------------------

    def _totals(self) -> dict[str, float]:
        out = dict(self._earlier._cumulative) if self._earlier else {}
        for k, v in self._cumulative.items():
            out[k] = out.get(k, 0.0) + v
        return out

    @property
    def wall_seconds(self) -> float:
        return time.perf_counter() - self._t_start

    def nonproductive_seconds(self) -> float:
        return sum(v for k, v in self._totals().items()
                   if k in self.non_productive)

    def goodput_fraction(self) -> float:
        """productive wall / total wall since construction, in [0, 1]."""
        wall = self.wall_seconds
        if wall <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.nonproductive_seconds() / wall))

    def goodput_summary(self) -> dict:
        """The ``goodput`` section of ``run_summary.json``."""
        wall = self.wall_seconds
        nonprod = self.nonproductive_seconds()
        # the start-up phases before the loop read as one entry, ``startup``
        breakdown: dict[str, float] = {}
        for k, v in self._totals().items():
            if k in self.non_productive and v > 0.0:
                k = "startup" if k in STARTUP_SPANS else k
                breakdown[k] = breakdown.get(k, 0.0) + v
        return {
            "wall_seconds": round(wall, 3),
            "productive_seconds": round(max(wall - nonprod, 0.0), 3),
            "nonproductive_seconds": round(nonprod, 3),
            "goodput_fraction": round(self.goodput_fraction(), 6),
            "breakdown_seconds": {
                k: round(v, 3) for k, v in sorted(breakdown.items())},
        }


# -- the start-up timeline ----------------------------------------------------


def _process_start(stat_path: str = "/proc/self/stat") -> Optional[float]:
    """The OS's start time of this process on ``time.perf_counter``'s clock
    (Linux: field 22 of ``/proc/self/stat``, ticks since boot, against
    ``CLOCK_BOOTTIME``); None where that cannot be read."""
    try:
        with open(stat_path) as f:
            # the fields after the command, which may itself hold ")" or " "
            fields = f.read().rsplit(")", 1)[1].split()
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age if age >= 0.0 else None


def _disjoint(intervals: list[tuple[str, float, float]], t0: float,
              t1: float) -> list[tuple[str, float, float]]:
    """``(name, begin, end)`` pieces of ``[t0, t1]``, disjoint and in order:
    where intervals overlap, the one that began last holds the overlap
    (``restart`` inside ``fit_prologue``)."""
    spans = [(n, max(b, t0), min(b + s, t1)) for n, b, s in intervals]
    spans = [x for x in spans if x[2] > x[1]]
    cuts = sorted({t0, t1, *(t for _, b, e in spans for t in (b, e))})
    pieces: list[tuple[str, float, float]] = []
    for a, b in zip(cuts, cuts[1:]):
        inside = [x for x in spans if x[1] <= a and b <= x[2]]
        if not inside:
            continue
        name = max(inside, key=lambda x: x[1])[0]
        if pieces and pieces[-1][0] == name and pieces[-1][2] == a:
            pieces[-1] = (name, pieces[-1][1], b)
        else:
            pieces.append((name, a, b))
    return pieces


def _short(name: str) -> str:
    """A phase's name as ``run_summary.json`` spells it."""
    return name.removeprefix("startup/")


def compile_sections(compiles: dict) -> dict:
    """``compiles`` (by phase) and ``compile_cache`` of the ``startup``
    section, from ``recompile.CompileLog.summary()``: written at the first
    boundary and again, for the whole run, at teardown."""
    return {
        "compiles": {_short(p): d for p, d in compiles["by_phase"].items()},
        "compile_cache": compiles["totals"],
    }


class StartupTimeline:
    """Process start to the end of the first logging boundary, as spans of one
    process-level :class:`SpanTimer` (``timer``) whose wall starts at the
    OS's process start (``origin`` says which start it found).  Code on the
    way brackets its work with :func:`startup_phase`; the first ``fit()`` of
    the process claims the timeline, continues its clock, and writes
    :meth:`section` at its first boundary.  No phase syncs the device: a phase
    is the host's wall time, and device work that outlives it lands in the
    phase that first waits."""

    def __init__(self, t_import: Optional[float] = None,
                 stat_path: str = "/proc/self/stat"):
        t_import = time.perf_counter() if t_import is None else t_import
        t_process = _process_start(stat_path)
        if t_process is not None and t_process > t_import:
            t_process = None
        self.origin = ("process_start" if t_process is not None
                       else "package_import")
        self.timer = SpanTimer()
        self.timer._t_start = t_import if t_process is None else t_process
        self.timer._intervals = []
        #: seconds of the import statements ``timed_import`` brackets
        self.imports_s: dict[str, float] = {}
        self.claimed = False  # a fit() took it
        self.closed = False   # its first boundary passed: nothing is added
        if t_process is not None:
            self.timer.add("startup/before_program", t_import - t_process,
                           begin=t_process)

    def close(self) -> None:
        self.closed = True
        self.timer._intervals = None

    def section(self, compiles: dict) -> Optional[dict]:
        """The ``startup`` section of ``run_summary.json``, read at the end of
        the first boundary (its ``host_sync`` has closed): the phases in
        order with what lies between them as ``unattributed``, in whole
        microseconds so that they sum to ``to_first_step_s`` exactly.
        ``compiles`` is ``recompile.CompileLog.summary()``."""
        intervals = list(self.timer._intervals or ())
        syncs = [(b, b + s) for n, b, s in intervals if n == "host_sync"]
        if not syncs:
            return None
        t0, t1 = self.timer._t_start, syncs[-1][1]
        first = min((b for n, b, _ in intervals if n == "dispatch"),
                    default=syncs[-1][0])
        self.timer.add(FIRST_STEP_SPAN, t1 - first, begin=first)
        intervals.append((FIRST_STEP_SPAN, first, t1 - first))
        kept = set(NON_PRODUCTIVE_SPANS) | {FIRST_STEP_SPAN}
        pieces = _disjoint([x for x in intervals if x[0] in kept], t0, t1)

        def us(t: float) -> int:  # whole microseconds since the origin
            return round((t - t0) * 1e6)

        total = us(t1)
        # each piece by its rounded ends, so the pieces stay disjoint
        pieces_us = [(_short(n), us(a), us(b)) for n, a, b in pieces]
        # every phase has its key, 0 where the run did not pass it
        by_name: dict[str, int] = {
            _short(n): 0
            for n in STARTUP_SPANS + ("restart", "compile", FIRST_STEP_SPAN)}
        for name, a, b in pieces_us:
            by_name[name] = by_name.get(name, 0) + b - a  # (or ``replan``)
        by_name["unattributed"] = total - sum(by_name.values())
        seconds = {k: v / 1e6 for k, v in by_name.items()}
        seconds["init_state"] = (
            by_name["init_params"] + by_name["init_opt_state"]) / 1e6
        totals = compiles["totals"]
        seconds["trace_lower"] = round(
            totals["trace_s"] + totals["lower_s"], 6)
        return {
            "origin": self.origin,
            "to_first_step_s": total / 1e6,
            "seconds": seconds,
            "unattributed_pct": round(
                100.0 * by_name["unattributed"] / max(total, 1), 3),
            "phases": [{"name": n, "begin_s": a / 1e6,
                        "seconds": (b - a) / 1e6}
                       for n, a, b in pieces_us if b > a],
            "imports_s": {k: round(v, 6) for k, v in self.imports_s.items()},
            **compile_sections(compiles),
        }


#: the process's timeline, created with the package's first import
#: (``neuronx_distributed_training_tpu/__init__.py`` stamps ``_T_IMPORT``
#: before it imports anything)
STARTUP = StartupTimeline(_package._T_IMPORT)


@contextmanager
def startup_phase(name: str) -> Iterator[None]:
    """Bracket a phase of the process's start-up timeline.  A no-op once the
    first ``fit()`` has passed its first boundary, and inside another open
    span (phases do not nest: the outer one counts)."""
    timeline = STARTUP
    if timeline.closed or _open:
        yield
        return
    with timeline.timer.span(name):
        yield


def startup_add(name: str, begin: float) -> None:
    """The stretch from ``begin`` (a ``time.perf_counter`` stamp) to now as a
    phase, under :func:`startup_phase`'s rules: for a stretch of code that no
    ``with`` block fits without re-indenting it."""
    timeline = STARTUP
    if not (timeline.closed or _open):
        timeline.timer.add(name, time.perf_counter() - begin, begin=begin)


@contextmanager
def timed_import(module: str) -> Iterator[None]:
    """``startup.imports_s[module]``: the seconds the bracketed import
    statement took (next to nothing when the module was loaded already)."""
    timeline, t0 = STARTUP, time.perf_counter()
    try:
        yield
    finally:
        if not timeline.closed:
            timeline.imports_s[module] = (
                timeline.imports_s.get(module, 0.0)
                + time.perf_counter() - t0)


def claim_startup() -> Optional[StartupTimeline]:
    """The process's start-up timeline, once: the first ``fit()`` takes it,
    a later one in the same process (tests, drills) gets None and starts its
    own clock."""
    timeline = STARTUP
    if timeline.claimed:
        return None
    timeline.claimed = True
    return timeline
