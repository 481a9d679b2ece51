"""Backend compiles (or compile-cache reads) that hit inside the window:
``compile_events`` of the trainer's ``run_summary.json`` holds
``{"step", "seconds"}`` for each one the run saw, ``step`` being the
trainer's step counter at the time.  The window's steps are those from the
one that opens it (``check_steps + warmup_steps``) to the last one inside
it.  A summary without the list (a program from before it) reads nothing.
"""

from benchmark.harness import say


def read(ctx):
    events = ctx["summary"].get("compile_events")
    if events is None or not ctx["rows"]:
        return None
    t = ctx["cell"].traffic
    lo = int(t["check_steps"]) + int(t["warmup_steps"])
    hi = max(int(r["step"]) for r in ctx["rows"])
    inside = [e for e in events if lo <= int(e["step"]) <= hi]
    for e in inside:
        say(f"compile in the window: step {e['step']}, {e['seconds']} s")
    return float(len(inside))
