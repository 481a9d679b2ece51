"""Share of its roofline that a group of kernels reached: the least time
the chip could take for the calls the step makes (``benchmark/flops.py``,
the chip's peaks) over the time the trace shows for them.

The kernels are the three of ``flops.flash_call``, found in the trace by
``trace_reduce.flash_kind``.  Calls per step are counted from the traffic
(micro-batches x layers, each on the rows one chip holds)."""

from benchmark import flops
from benchmark.harness import say


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr["steps"] or ctx["peaks"] is None:
        return None
    cell = ctx["cell"]
    t = cell.traffic
    rows_per_call = (int(t["global_batch_size"]) // int(t["micro_batches"])
                     // ctx["data_parallel"])
    calls = int(t["micro_batches"]) * int(cell.model["num_layers"])
    need = flops.flash_call(cell.model, int(t["seq_length"]), rows_per_call)
    least = took = 0.0
    for kind in need:
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
