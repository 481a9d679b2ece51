"""A statistic of one column of the trainer's ``metrics.jsonl`` over the
window's steps (host spans such as ``time/data_wait`` are columns there)."""

import statistics

from benchmark.harness.stats import quantile95

STATS = {"p95": quantile95, "mean": statistics.fmean, "median": statistics.median}


def read(ctx, *, key, stat="p95", scale=1.0):
    values = [float(r[key]) for r in ctx["rows"] if key in r]
    if not values:
        return None
    return STATS[stat](values) * scale
