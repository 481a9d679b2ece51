"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick's own count: it does not move with the program.  Conventions:

- a matmul of ``[m, k] x [k, n]`` is ``2 m k n`` operations;
- backward = 2 x forward, so a trained token needs 3 x its forward
  operations; recomputed operations (activation checkpointing, the flash
  backward's second look at the scores) are NOT required operations;
- only the experts a token is routed to count (``top_k`` of them);
- a causal query at position ``i`` sees ``min(i + 1, sliding_window)`` keys;
  the mean over a sequence is :func:`mean_visible_keys`;
- embedding lookups, norms, RoPE, softmax and the optimizer are not counted.

The program's own ``utils/perf.py`` uses ``seq_len / 2`` keys and ignores the
window (PERF.md, Open questions); nothing here reads it.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Mapping, Optional

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; an unknown kind raises:
    a device that is not in the table is an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name} "
            f"(known: {sorted(table)}); add its published peaks with a source")
    return table[device_kind]


def mean_visible_keys(seq_len: int, window: Optional[int]) -> float:
    """Mean number of keys a causal query sees: ``(s + 1) / 2`` without a
    window; with one, query ``i`` sees ``min(i + 1, window)``."""
    s = int(seq_len)
    w = s if not window else min(int(window), s)
    return (w * (w + 1) / 2 + (s - w) * w) / s


def model_dims(model: Mapping[str, Any]) -> dict:
    """The sizes the counts need, read from a trainer config's ``model``
    block (HF key names, as ``benchmark/configs/*.json`` hold them)."""
    nh = int(model["num_attention_heads"])
    h = int(model["hidden_size"])
    moe = model.get("moe") or {}
    return {
        "hidden": h,
        "ffn": int(model["intermediate_size"]),
        "layers": int(model["num_layers"]),
        "heads": nh,
        "kv_heads": int(model.get("num_key_value_heads") or nh),
        "head_dim": int(model.get("head_dim") or h // nh),
        "vocab": int(model["vocab_size"]),
        "window": model.get("sliding_window"),
        "experts": int(moe.get("num_experts", 0) or 0),
        "top_k": int(moe.get("top_k", 0) or 0),
    }


def matmul_params_per_token(d: Mapping[str, Any]) -> dict:
    """Weights a token is multiplied with, per layer and in the head."""
    h, f, dh = d["hidden"], d["ffn"], d["head_dim"]
    attn = h * (d["heads"] + 2 * d["kv_heads"]) * dh + d["heads"] * dh * h
    mlp = 3 * h * f
    if d["experts"]:
        mlp = d["top_k"] * mlp + h * d["experts"]  # activated experts + router
    return {"layer": attn + mlp, "head": h * d["vocab"]}


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token."""
    d = model_dims(model)
    p = matmul_params_per_token(d)
    keys = mean_visible_keys(seq_len, d["window"])
    attn_fwd = 4 * d["heads"] * d["head_dim"] * keys  # QK^T and PV
    dense = 6 * (d["layers"] * p["layer"] + p["head"])
    attention = 3 * d["layers"] * attn_fwd
    return {"total": dense + attention, "dense": dense, "attention": attention,
            "head": 6 * p["head"], "mean_keys": keys}


#: matmuls over the visible scores in each flash kernel call: forward QK^T,
#: PV; dq recomputes QK^T, then dO V^T and dS K; dkv recomputes QK^T, then
#: P^T dO, dO V^T and dS^T Q.  The recomputed QK^T is part of the flash
#: algorithm (the scores are never stored), so it counts for the KERNEL's
#: roofline, though not for a trained token's required operations.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call(model: Mapping[str, Any], seq_len: int, batch: int,
               itemsize: int = 2) -> dict:
    """Operations and bytes of one call of each flash kernel on
    ``[batch, seq, heads, head_dim]`` operands (causal, window-capped).
    Bytes are each operand read once and each result written once."""
    d = model_dims(model)
    b, s, nh, nkv, dh = batch, int(seq_len), d["heads"], d["kv_heads"], d["head_dim"]
    visible = b * nh * s * mean_visible_keys(s, d["window"])
    q = b * nh * s * dh * itemsize
    kv = b * nkv * s * dh * itemsize
    row = b * nh * s * 4  # lse / delta, float32
    bytes_ = {
        "fwd": q + 2 * kv + q + row,                   # q k v -> o lse
        "dq": q + 2 * kv + q + 2 * row + q,            # q k v do lse delta -> dq
        "dkv": q + 2 * kv + q + 2 * row + 2 * kv,      # q k v do lse delta -> dk dv
    }
    return {k: {"flops": 2 * m * visible * dh, "bytes": bytes_[k]}
            for k, m in FLASH_MATMULS.items()}


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel: ``flops`` and ``bytes`` of one call
    (:func:`flash_call` on the rows one chip holds of a micro-batch) and the
    ``calls`` a step makes of it: micro-batches x layers."""
    rows = (int(traffic["global_batch_size"]) // int(traffic["micro_batches"])
            // int(data_parallel))
    calls = int(traffic["micro_batches"]) * int(model["num_layers"])
    return {kind: {**need, "calls": calls} for kind, need in flash_call(
        model, int(traffic["seq_length"]), rows).items()}


def roofline_seconds(flops: float, bytes_: float, peaks: Mapping[str, Any]) -> dict:
    """Least time the chip could take, and which bound holds."""
    t_c = flops / float(peaks["bf16_flops_per_s"])
    t_m = bytes_ / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
