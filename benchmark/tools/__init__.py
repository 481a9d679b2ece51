"""Hand-run tools of the benchmark (never part of a run)."""
