"""Share of their roofline that the kernels under ONE named component of the
program's name stacks reached: the least time the chip could take for the
calls a step makes of them (the configuration's ``operations.<calls>``, which
returns, per kernel, ``flops`` and ``bytes`` of one call and its ``calls`` a
step, as ``kernel_calls`` does for the flash kernels; the chip's peaks) over
the self time the trace shows under the component (``inner_scope``: every
operation whose name stack holds it, so what the program does around the
kernels under the same scope counts against them).

Reads nothing where the configuration's operations have no such function, or
where no operation of the trace names the component (a program from before
it)."""

from __future__ import annotations

from benchmark import flops, trace_reduce
from benchmark.harness import say
from benchmark.readers.inner_scope import reduce_component


def read(ctx, *, component, calls):
    xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
    count = getattr(ctx["cell"].operations, calls, None)
    if ctx.get("trace") is None or xplane is None or count is None or ctx["peaks"] is None:
        return None
    took = reduce_component(xplane, component)
    if not took:
        return None
    cell = ctx["cell"]
    need = count(cell.model, cell.traffic, ctx["data_parallel"])
    least = 0.0
    for kind, k in need.items():
        roof = flops.roofline_seconds(k["flops"], k["bytes"], ctx["peaks"])
        say(f"roofline: {component} {kind}: {k['calls']} call(s) a step, least "
            f"{k['calls'] * roof['seconds'] * 1e3:.3f} ms ({roof['bound']}-bound)")
        least += k["calls"] * roof["seconds"]
    say(f"roofline: {component}: {took * 1e3:.3f} ms a step, least {least * 1e3:.3f} ms")
    return 100.0 * least / took
