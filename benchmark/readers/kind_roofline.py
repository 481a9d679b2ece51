"""Share of their roofline that the flash kernels of ONE kind of layer
reached, where a stack's layers differ: the least time the chip could take
for the calls that kind makes a step (the configuration's
``operations.kernel_calls_by_type``, the chip's peaks) over the self time the
trace shows for the kernels under that kind's inner scope (``scope``: the
component the program puts around a layer of the kind, the kernels' own
scopes inside it).

Reads nothing where the configuration's operations count no kinds, or where
no operation of the trace names the scope (a program from before it)."""

from __future__ import annotations

from benchmark import flops, trace_reduce, xplane_meta
from benchmark.harness import say
from benchmark.readers.scope_time import SCOPES, components, window

KERNELS = SCOPES["attention"]   # flash_fwd, flash_dq, flash_dkv


def kernel_seconds(path, scope: str):
    """Self seconds per step, mean over chips, of the operations whose name
    stack holds ``scope`` and one of the kernels' scopes; None without a
    window of steps or without such an operation."""
    raw = trace_reduce.load(path)
    win = window(raw)
    if not raw["chips"] or win is None:
        return None
    lo, hi, steps, _ = win
    inside = {name for name, op in xplane_meta.tf_ops(path).items()
              if scope in (parts := components(op)) and any(k in parts for k in KERNELS)}
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


def read(ctx, *, scope, attention_type):
    xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
    by_type = getattr(ctx["cell"].operations, "kernel_calls_by_type", None)
    if ctx.get("trace") is None or xplane is None or by_type is None or ctx["peaks"] is None:
        return None
    cell = ctx["cell"]
    need = by_type(cell.model, cell.traffic, ctx["data_parallel"]).get(attention_type)
    took = kernel_seconds(xplane, scope)
    if not need or not took:
        return None
    least = sum(k["calls"] * flops.roofline_seconds(k["flops"], k["bytes"], ctx["peaks"])["seconds"]
                for k in need.values())
    say(f"roofline: {scope}: kernels {took * 1e3:.3f} ms a step, least {least * 1e3:.3f} ms")
    return 100.0 * least / took
