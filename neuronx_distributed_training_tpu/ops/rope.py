"""Rotary position embeddings.

Covers the reference's two RoPE implementations: the HF-style
``LlamaRotaryEmbedding`` with fp64-precision inv-freq override
(``modeling_llama.py:847-873``) and Megatron's ``rotary_pos_embedding.py`` with
position-interpolation and ABF base scaling (``rotary_pos_embedding.py:22-81``),
plus the HF ``yarn`` frequencies and a partial rotary factor (``models/laguna.py``)
and the interleaved pairing (``rope_interleave``, ``models/kanana.py``).
Frequencies are computed in fp64 on host at trace time (static) then applied in
fp32 — matching the reference's precision discipline without any global flag.

Context parallelism offsets positions per CP shard (reference
``modeling_llama.py:619-629``); callers pass explicit ``positions`` so the same
code serves CP, packed sequences, and inference.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    *,
    theta: float = 10000.0,
    position_interpolation_factor: float | None = None,
    abf_scale: float | None = None,
    partial_rotary_factor: float = 1.0,
    yarn: Mapping[str, Any] | None = None,
) -> np.ndarray:
    """Inverse frequencies ``[rotary_dim/2]`` in fp64 (host-side, static).

    ``abf_scale`` scales the base theta (adjusted-base-frequency, reference
    ``rotary_pos_embedding.py``); ``position_interpolation_factor`` divides
    positions at application time.  ``partial_rotary_factor`` < 1 rotates the
    first ``head_dim * factor`` dims only (``apply_rope`` passes the rest
    through).  ``yarn`` (HF ``rope_type: yarn``: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``) blends
    each dim between its frequency divided by ``factor`` and its own, by the
    linear ramp between the dims whose wavelengths make ``beta_fast`` and
    ``beta_slow`` turns in the original context; the frequencies are static,
    so they hold at any sequence length.  Its ``attention_factor`` is
    ``rope_cos_sin``'s ``scale``.
    """
    base = float(theta)
    if abf_scale is not None:
        base = base * abf_scale
    dim = int(head_dim * partial_rotary_factor)
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq = 1.0 / (base**exponent)
    if position_interpolation_factor:
        inv_freq = inv_freq / float(position_interpolation_factor)
    if yarn:
        original = float(yarn["original_max_position_embeddings"])

        def turns_dim(turns: float) -> float:  # the dim that makes ``turns`` turns
            return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(turns_dim(float(yarn.get("beta_fast", 32)))), 0)
        high = min(math.ceil(turns_dim(float(yarn.get("beta_slow", 1)))), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
        inv_freq = inv_freq / float(yarn["factor"]) * ramp + inv_freq * (1.0 - ramp)
    return inv_freq


def yarn_attention_factor(yarn: Mapping[str, Any]) -> float:
    """What YaRN multiplies cos and sin by: the stated ``attention_factor``,
    else ``0.1 ln(factor) + 1``."""
    stated = yarn.get("attention_factor")
    return float(stated) if stated else 0.1 * math.log(float(yarn["factor"])) + 1.0


def rope_cos_sin(
    positions: jax.Array,  # [batch, seq] or [seq]
    inv_freq: np.ndarray,
    *,
    dtype=jnp.float32,
    scale: float | None = None,
):
    """cos/sin tables for given positions: ``[..., seq, rotary_dim/2]``, both
    times ``scale`` (YaRN's attention factor) where given."""
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale is not None and scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return cos.astype(dtype), sin.astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x: [batch, seq, heads, head_dim]`` (HF half-rotation layout).

    cos/sin are ``[batch, seq, rotary_dim/2]`` (or ``[seq, rotary_dim/2]``);
    where ``rotary_dim < head_dim`` (a partial rotary factor) the first
    ``rotary_dim`` dims are rotated in their own halves and the rest pass.
    """
    orig_dtype = x.dtype
    half = cos.shape[-1]
    if 2 * half < x.shape[-1]:
        rotated = apply_rope(x[..., :2 * half], cos, sin)
        return jnp.concatenate([rotated, x[..., 2 * half:]], axis=-1)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    if cos.ndim == 2:  # [seq, half] -> broadcast over batch
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    else:  # [batch, seq, half]
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]
    out1 = x1 * cos_b - x2 * sin_b
    out2 = x2 * cos_b + x1 * sin_b
    return jnp.concatenate([out1, out2], axis=-1).astype(orig_dtype)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``apply_rope`` where the source pairs neighbours, ``(x[2i], x[2i + 1])``
    (HF ``rope_interleave``): the dims are brought into the half layout first
    (evens, then odds) and stay there.  A score is a sum over the dims of q
    and k alike, so the order they are left in does not show in it."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1), cos, sin)
