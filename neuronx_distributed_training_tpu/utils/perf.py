"""Analytic FLOPs / MFU estimation — the TPU-native analogue of the reference's
``utils/llama_perf_estimate.py`` (FLOPs model at reference
``llama_perf_estimate.py:48-69``, peak-FLOPs table at ``:89-97``).

FWD FLOPs = num_layers * (attention + mlp) + embedding/logits matmuls;
BWD = 2 x FWD (same convention as the reference).  Peak FLOPs come from a
per-TPU-generation table instead of the reference's trn1/trn2 numbers.
"""

from __future__ import annotations

from typing import Any

import jax

# Peak bf16 TFLOP/s per chip by TPU generation (public figures).
# Ordered most-specific-first: device_kind strings like "TPU v5 lite" must
# match their own entry before the bare-generation one.
PEAK_TFLOPS_PER_CHIP = {
    "v5 lite": 197.0,  # v5e device_kind spells it out
    "v5e": 197.0,
    "lite": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,  # Trillium
    "v6": 918.0,
    "v4": 275.0,
    "v5": 459.0,
    # nominal figure for the autotune planner's off-hardware "cpu" topology
    # only; ``detect_peak_tflops`` never returns it (no MFU on a CPU)
    "cpu": 0.5,
}


def detect_peak_tflops(device: jax.Device | None = None) -> float | None:
    """Peak bf16 TFLOP/s of ``device``'s TPU generation.  ``None`` off the
    TPU: there is no peak to hold a CPU run against, so no MFU is derived
    there.  A TPU whose ``device_kind`` is not in the table raises — a
    default would price it with another chip's peak."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, tf in PEAK_TFLOPS_PER_CHIP.items():
        if key in kind:
            return tf
    raise ValueError(
        f"unknown TPU device_kind {device.device_kind!r}: add its peak to "
        f"utils.perf.PEAK_TFLOPS_PER_CHIP (known: "
        f"{', '.join(k for k in PEAK_TFLOPS_PER_CHIP if k != 'cpu')})"
    )


#: component keys of the per-token FLOPs breakdown, in reporting order
FLOPS_COMPONENTS = ("attention", "mlp", "router", "head")


def _attention_flops_per_token(
    *, hidden_size: int, num_attention_heads: int, num_kv_heads: int | None,
    seq_len: int, head_dim: int | None = None,
    include_causal_half: bool = True,
) -> float:
    """Per-layer attention FLOPs/token: qkv + o projections + the causal
    score/context matmuls — the Llama accounting with the MLP term removed.
    Shared by every family's ``flops_breakdown`` (``models/family.py``)."""
    h = hidden_size
    d = head_dim or h // num_attention_heads
    nh = num_attention_heads
    nkv = num_kv_heads or nh
    qkv = 2 * h * (nh + 2 * nkv) * d
    o = 2 * nh * d * h
    attn_scores = 2 * seq_len * nh * d
    attn_context = 2 * seq_len * nh * d
    if include_causal_half:
        attn_scores /= 2
        attn_context /= 2
    return qkv + o + attn_scores + attn_context


def llama_flops_per_token(
    *,
    num_layers: int,
    hidden_size: int,
    intermediate_size: int,
    num_attention_heads: int,
    num_kv_heads: int | None,
    vocab_size: int,
    seq_len: int,
    head_dim: int | None = None,
    include_causal_half: bool = True,
) -> float:
    """Forward FLOPs per token of a Llama-style decoder.

    Matches the reference's accounting (``llama_perf_estimate.py:48-69``):
    per-layer attention projections + score/context matmuls + SwiGLU MLP,
    plus the lm_head matmul.  ``include_causal_half`` halves the attention
    score/context term (causal masking skips half the work — flash kernels
    exploit this; the reference's estimate does the same).
    """
    attn = _attention_flops_per_token(
        hidden_size=hidden_size, num_attention_heads=num_attention_heads,
        num_kv_heads=num_kv_heads, seq_len=seq_len, head_dim=head_dim,
        include_causal_half=include_causal_half)
    mlp = 2 * hidden_size * (3 * intermediate_size)  # gate, up, down
    return num_layers * (attn + mlp) + 2 * hidden_size * vocab_size


def train_step_flops_per_token(fwd_flops_per_token: float) -> float:
    """fwd + bwd, bwd = 2x fwd (reference convention)."""
    return 3.0 * fwd_flops_per_token


def mfu(
    tokens_per_sec_per_chip: float,
    flops_per_token: float,
    peak_tflops_per_chip: float,
) -> float:
    """Model FLOPs utilization in [0, 1]."""
    achieved = tokens_per_sec_per_chip * flops_per_token
    return achieved / (peak_tflops_per_chip * 1e12)


class Throughput:
    """Moving-average sequences/sec with peak tracking, mirroring the
    reference's ``Throughput`` (``utils/utils.py:52-77``, window=10).

    ``peak`` is only recorded once the window holds at least
    ``min(window, 3)`` samples: the first one or two windows average over a
    partial history and a single fast boundary there would pin a phantom
    peak no steady-state window can ever reach again.

    ``seq_len`` (when given) makes ``tokens_per_sec`` the one source of
    truth tokens-based metrics (MFU, tokens/sec/chip) derive from.
    """

    def __init__(self, batch_size: int, window: int = 10, seq_len: int = 0):
        self.batch_size = batch_size
        self.window = window
        self.seq_len = int(seq_len or 0)
        self._times: list[float] = []
        self.peak = 0.0
        self.last = 0.0
        self.total_seqs = 0

    def update(self, step_seconds: float, num_steps: int = 1) -> float:
        self._times.append(step_seconds)
        if len(self._times) > self.window:
            self._times.pop(0)
        self.total_seqs += self.batch_size * num_steps
        tput = self.batch_size * len(self._times) / sum(self._times)
        self.last = tput
        if len(self._times) >= min(self.window, 3):
            self.peak = max(self.peak, tput)
        return tput

    @property
    def tokens_per_sec(self) -> float:
        """Windowed tokens/sec (seqs/s x seq_len); 0.0 when seq_len unset."""
        return self.last * self.seq_len


def flops_for_config(model_cfg: Any, seq_len: int) -> float:
    """fwd FLOPs/token from a LlamaConfig-like object."""
    return llama_flops_per_token(
        num_layers=model_cfg.num_layers,
        hidden_size=model_cfg.hidden_size,
        intermediate_size=model_cfg.intermediate_size,
        num_attention_heads=model_cfg.num_attention_heads,
        num_kv_heads=getattr(model_cfg, "num_kv_heads", None),
        vocab_size=model_cfg.vocab_size,
        seq_len=seq_len,
        head_dim=getattr(model_cfg, "head_dim", None),
    )
