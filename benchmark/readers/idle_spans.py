"""Whose time the device's idle time is, in the program's own words: the
trainer's ``SpanTimer`` spans are ``jax.profiler.TraceAnnotation`` events on
the loop thread of the host plane, on the device trace's clock.  The share of
the window's idle time (each chip's gaps between operations, first to last
step annotation, mean over chips) that none of those spans covers is what
the program cannot account for itself.  A trace without one such annotation
(a program from before them) reads nothing.
"""

from __future__ import annotations

from typing import Optional

from benchmark import trace_reduce
from benchmark.harness import say
from benchmark.readers.scope_time import window

#: the program's ``telemetry/spans.py`` span names
SPANS = ("data_wait", "dispatch", "compile", "host_sync", "log_metrics",
         "validate", "checkpoint", "restart")


def _overlap(gaps: list, spans: list) -> float:
    """Length of the gaps' intersection with the (merged) spans."""
    total, j = 0.0, 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def reduce_idle(path) -> Optional[dict]:
    """Seconds per step, mean over chips: ``idle``, ``by_span`` (a span's
    part of it) and ``unattributed`` (idle under no span at all)."""
    raw = trace_reduce.load(path)
    win = window(raw)
    if not raw["chips"] or win is None:
        return None
    lo, hi, steps, main = win
    by_name = {s: trace_reduce._union(
        c for n, a, b in main
        if n == s and (c := trace_reduce._clip((a, b), lo, hi)) is not None)
        for s in SPANS}
    if not any(by_name.values()):
        return None
    every = trace_reduce._union(ab for evs in by_name.values() for ab in evs)
    n = len(raw["chips"]) * steps * 1e9
    out = {"idle": 0.0, "unattributed": 0.0, "by_span": dict.fromkeys(SPANS, 0.0)}
    for ops in raw["chips"].values():
        merged = trace_reduce._union(
            c for _, a, b in ops
            if (c := trace_reduce._clip((a, b), lo, hi)) is not None)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle = sum(b - a for a, b in gaps)
        out["idle"] += idle / n
        out["unattributed"] += (idle - _overlap(gaps, every)) / n
        for s in SPANS:
            out["by_span"][s] += _overlap(gaps, by_name[s]) / n
    return out


def read(ctx):
    xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
    if ctx.get("trace") is None or xplane is None:
        return None
    idle = reduce_idle(xplane)
    if idle is None or idle["idle"] <= 0:
        return None
    for s, seconds in idle["by_span"].items():
        if seconds > 0:
            say(f"idle: {seconds * 1e3:.3f} ms a step under {s}")
    say(f"idle: {idle['unattributed'] * 1e3:.3f} of {idle['idle'] * 1e3:.3f} ms "
        f"a step under no span of the program")
    return 100.0 * idle["unattributed"] / idle["idle"]
