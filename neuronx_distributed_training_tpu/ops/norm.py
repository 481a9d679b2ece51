"""Normalization layers.

RMSNorm with fp32 internals regardless of compute dtype — the reference's
``LlamaRMSNorm`` upcasts to the cast-dtype before the variance reduction
(``modeling_llama.py:145-161``); here the upcast is explicit and local.
The fused-kernel concern of ``fused_layer_norm.py`` (apex MixedFusedLayerNorm /
MixedFusedRMSNorm, reference ``fused_layer_norm.py:14-36``) is handled by XLA
fusion on TPU; a Pallas fused variant exists for the flash-attention path where
profiling warrants it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def init_rms_norm(hidden: int, *, dtype=jnp.float32):
    return {"scale": jnp.ones((hidden,), dtype)}, {"scale": P(None)}


def apply_rms_norm(params, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * params["scale"].astype(jnp.float32)).astype(orig_dtype)


def apply_gated_rms_norm(params, y: jax.Array, z: jax.Array, *, groups: int,
                         eps: float = 1e-5) -> jax.Array:
    """``RMS_group(y * silu(z)) * scale`` (a Mamba-2 mixer's norm,
    models/nemotron_h.py): the gate first, then an RMS norm inside each of
    ``groups`` equal groups of the channels, one learned scale for all of
    them; fp32 internals as ``apply_rms_norm``.  The groups' mean squares are
    taken, and handed back to their channels, as products with a ``[channels,
    groups]`` table of ones at ``highest`` precision: a reshape of the lanes
    into ``[groups, width]`` is a copy on the TPU (25 ms of a 640 ms step at
    ``[16384, 4096]`` in 8 groups: PERF.md section 6, PR 46)."""
    width = y.shape[-1]
    member = jnp.where(jnp.arange(width)[:, None] // (width // groups)
                       == jnp.arange(groups)[None, :], 1.0, 0.0)
    highest = jax.lax.Precision.HIGHEST

    @jax.checkpoint   # the backward pass keeps y and z, not the float32 chain
    def norm(scale, y, z):
        yf, zf, sf = (a.astype(jnp.float32) for a in (y, z, scale))  # jaxlint: disable=JL106
        gated = yf * jax.nn.silu(zf)
        var = jnp.matmul(gated * gated, member, precision=highest) * (groups / width)
        inv = jnp.matmul(jax.lax.rsqrt(var + eps), member.T, precision=highest)
        return (gated * inv * sf).astype(y.dtype)

    return norm(params["scale"], y, z)


def init_layer_norm(hidden: int, *, dtype=jnp.float32):
    return (
        {"scale": jnp.ones((hidden,), dtype), "bias": jnp.zeros((hidden,), dtype)},
        {"scale": P(None), "bias": P(None)},
    )


def apply_layer_norm(params, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = normed * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(orig_dtype)
