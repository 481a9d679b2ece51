"""Device time of one named component of the program's name stacks, wherever
it sits: the self time (``trace_reduce._self_times``) of every operation whose
``tf_op`` holds ``component`` as one of its parts, bare or wrapped
(``scope_time.components``), inside the window ``scope_time.window`` gives,
as a mean over chips, in ms per step.

``scope_time`` reads the scopes its own table knows, by their path from a
top-level scope.  This reader asks for one name only, so an inner scope that
a later program adds under a scope the table already has (where its time
stays counted, so nothing becomes ``unscoped``) can be read without a new
table.  A trace in which no operation names the component (a program from
before it) reads nothing.
"""

from __future__ import annotations

from typing import Optional

from benchmark import trace_reduce, xplane_meta
from benchmark.harness import say
from benchmark.readers.scope_time import components, window


def reduce_component(path, component: str) -> Optional[float]:
    """Self seconds per step under ``component``, mean over chips; None
    without a window of steps or without one operation that names it."""
    raw = trace_reduce.load(path)
    win = window(raw)
    if not raw["chips"] or win is None:
        return None
    lo, hi, steps, _ = win
    tf = xplane_meta.tf_ops(path)
    inside = {name for name, op in tf.items() if component in components(op)}
    if not inside:
        return None
    n = len(raw["chips"]) * steps * 1e9
    total = 0.0
    for ops in raw["chips"].values():
        clipped = [(name, *c) for name, a, b in ops
                   if (c := trace_reduce._clip((a, b), lo, hi)) is not None]
        total += sum(ns for name, ns in trace_reduce._self_times(clipped)
                     if name in inside) / n
    return total


def read(ctx, *, component):
    xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
    if ctx.get("trace") is None or xplane is None:
        return None
    seconds = reduce_component(xplane, component)
    if not seconds:
        return None
    say(f"scopes: component {component} {seconds * 1e3:.3f} ms a step")
    return 1e3 * seconds
