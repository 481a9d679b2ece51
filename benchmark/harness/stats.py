"""Statistics the benchmark reports."""

import math


def quantile95(values: list) -> float:
    """The 95th percentile, nearest rank from above."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, math.ceil(0.95 * len(xs)) - 1)]
