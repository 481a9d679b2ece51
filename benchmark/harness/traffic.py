"""The one general traffic generator: rows of tokens from a seed.

A traffic mix is a data file under ``benchmark/traffic/``; this module turns
its ``tokens`` block into rows.  A row is a pure function of
``(seed, row index)``, so the trainer's sampler, the reference and a rerun
all see the same tokens, and all rows differ.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np


def token_rows(spec: Mapping[str, Any], seed: int, indices, seq_len: int,
               vocab: int) -> np.ndarray:
    """``[len(indices), seq_len]`` int32 token ids for the given row indices."""
    kind = spec.get("kind", "uniform")
    if kind != "uniform":
        raise ValueError(f"traffic: unknown tokens.kind {kind!r} (known: uniform)")
    lo = int(spec.get("low", 0))
    hi = int(spec.get("high", 0) or vocab)
    rows = np.empty((len(indices), seq_len), dtype=np.int32)
    for r, i in enumerate(indices):
        rng = np.random.Generator(np.random.PCG64([int(seed), int(i)]))
        rows[r] = rng.integers(lo, hi, seq_len, dtype=np.int32)
    return rows


def step_tokens(spec: Mapping[str, Any], seed: int, step: int, *, seq_len: int,
                vocab: int, global_batch: int, micro_batches: int) -> np.ndarray:
    """Tokens of optimizer step ``step`` (from 0) as ``[micro, rows, seq]``:
    the rows a sequential sampler hands the trainer for that step."""
    idx = range(step * global_batch, (step + 1) * global_batch)
    rows = token_rows(spec, seed, idx, seq_len, vocab)
    return rows.reshape(micro_batches, global_batch // micro_batches, seq_len)
