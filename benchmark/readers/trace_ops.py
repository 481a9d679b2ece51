"""Device time from the reduced trace: the idle share, or milliseconds per
step of the flash kernels (``trace_reduce.flash_kind``) or of the collective
operations (the union of their intervals, mean over chips)."""


def read(ctx, *, what):
    tr = ctx.get("trace")
    if tr is None:
        return None
    if what == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if not tr["steps"]:
        return None
    if what == "collective_ms_per_step":
        if ctx["chips"] < 2:
            return None
        return 1e3 * tr["collective_s"] / tr["steps"]
    if what == "flash_ms_per_step":
        seconds = sum(tr["flash_s"].values())
        return 1e3 * seconds / tr["steps"] if seconds > 0 else None
    raise ValueError(f"trace_ops: unknown what={what!r}")
