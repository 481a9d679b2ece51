"""Share of its roofline that a group of kernels reached: the least time
the chip could take for the calls the step makes (``benchmark/flops.py``,
the chip's peaks) over the time the trace shows for them.

The kernels, their operations and bytes a call and their calls a step on one
chip are the configuration's ``operations.kernel_calls`` (``harness/cell.py``);
their time is what ``trace_reduce.flash_kind`` finds in the trace under the
same kinds."""

from benchmark import flops
from benchmark.harness import say


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr["steps"] or ctx["peaks"] is None:
        return None
    cell = ctx["cell"]
    need = cell.operations.kernel_calls(
        cell.model, cell.traffic, ctx["data_parallel"])
    least = took = 0.0
    for kind in need:
        calls = need[kind]["calls"]
        seconds = tr["flash_s"].get(kind, 0.0) / tr["steps"]
        if seconds <= 0:
            return None
        roof = flops.roofline_seconds(
            need[kind]["flops"], need[kind]["bytes"], ctx["peaks"])
        say(f"roofline: {kind}: {calls} call(s) a step, {seconds * 1e3:.3f} ms, "
            f"least {calls * roof['seconds'] * 1e3:.3f} ms ({roof['bound']}-bound)")
        least += calls * roof["seconds"]
        took += seconds
    return 100.0 * least / took
