"""Mixture-of-Experts: routers, expert compute (dropped & dropless), aux losses.

TPU-native re-design of the NxD MoE stack the reference consumes
(``RouterTopK`` / ``RouterSinkhorn`` + ``ExpertMLPs`` + ``MoE`` modules, built at
reference ``modeling_mixtral.py:342-374`` and ``transformer.py:376-467``, with
the dropped-vs-dropless validation at ``training_orchestrator.py:60-102``):

- **router**: top-k softmax routing (Mixtral) or sinkhorn (Megatron top-1)
  over token logits; router always computed in fp32 (routing decisions must
  not flip under bf16);
- **dropped** (capacity factor): dense dispatch/combine einsums against a
  ``[tokens, experts, capacity]`` one-hot — MXU-friendly, static shapes,
  tokens beyond ``capacity_factor * tokens/experts`` per expert are dropped
  exactly like the reference's ``ExpertMLPs(capacity_factor=...)``;
- **dropless**: sort-by-expert + ``jax.lax.ragged_dot`` grouped matmul — every
  token is processed regardless of load (the reference's
  ``dropless=True`` mode), no capacity hyperparameter;
- **aux load-balancing loss**: Mixtral's ``load_balancing_loss_func``
  (reference ``modeling_mixtral.py:872-878``) — mean(expert_fraction *
  router_prob_fraction) * num_experts, plus optional router z-loss;
- **EP**: expert-major weight tensors carry their expert dim sharded over the
  ``expert`` mesh axis (see ``expert_specs``); GSPMD inserts the
  all-to-alls the reference gets from NxD's token-shuffle machinery.

SwiGLU experts (``glu_mlp`` in the reference): w_gate/w_up fused as one
``[E, h, 2*ff]`` tensor, w_down ``[E, ff, h]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mirrors the reference's ``model.moe`` YAML block
    (``hf_mixtral_8x7b_config.yaml:45-52``, ``megatron_gpt_model.py:133-147``)."""

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: Optional[float] = None  # None/0 -> dropless
    dropless: bool = True
    router_type: str = "top_k"  # "top_k" | "sinkhorn"
    router_aux_loss_coef: float = 0.02
    router_z_loss_coef: float = 0.0
    normalize_top_k_affinities: bool = True  # Mixtral renormalizes top-k probs
    sinkhorn_iterations: int = 8
    # de-bias capacity drops from sequence position (reference
    # token_shuffle_group_size, transformer.py:410-411); dropped path only
    token_shuffle_group_size: int = 0

    @classmethod
    def from_config(cls, moe_cfg: dict[str, Any]) -> "MoEConfig":
        m = dict(moe_cfg or {})
        cap = m.get("capacity_factor")
        dropless = bool(m.get("dropless", not cap))
        return cls(
            num_experts=int(m.get("num_experts", m.get("num_moe_experts", 8))),
            top_k=int(m.get("top_k", m.get("moe_top_k", 2))),
            capacity_factor=None if dropless else float(cap or 1.0),
            dropless=dropless,
            router_type=str(m.get("router_type", "top_k")),
            router_aux_loss_coef=float(m.get("router_aux_loss_coef", 0.02)),
            router_z_loss_coef=float(m.get("router_z_loss_coef", 0.0)),
            normalize_top_k_affinities=bool(m.get("normalize_top_k_affinities", True)),
            token_shuffle_group_size=int(m.get("token_shuffle_group_size", 0) or 0),
        )


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_moe_params(key: jax.Array, hidden: int, ffn: int, cfg: MoEConfig,
                    dtype=jnp.float32, stddev: float = 0.02):
    """Router + fused SwiGLU expert weights, expert-major ``[E, ...]``."""
    kr, kgu, kd = jax.random.split(key, 3)
    e = cfg.num_experts
    return {
        "router": {"w": (jax.random.normal(kr, (hidden, e)) * stddev).astype(jnp.float32)},
        "experts": {
            "gate_up": (jax.random.normal(kgu, (e, hidden, 2 * ffn)) * stddev).astype(dtype),
            "down": (jax.random.normal(kd, (e, ffn, hidden)) * stddev).astype(dtype),
        },
    }


def moe_param_specs(cfg: MoEConfig):
    """Expert dim over ``expert`` axis (EP); ffn dim over ``model`` (TP inside
    each expert) — composing EP x TP exactly like NxD's expert sharding."""
    return {
        "router": {"w": P(None, None)},
        "experts": {
            "gate_up": P("expert", None, "model"),
            "down": P("expert", "model", None),
        },
    }


def group_interleaved_stack(moe_frequency: int, layer_stack):
    """Split a grouped dense/MoE layer stack into scan inputs.

    Layout shared by the mixtral and gpt families for ``moe_frequency > 1``:
    attn/norm leaves are flat ``[L, ...]``, ``mlp`` is ``{"moe": [G, ...],
    "dense": [G, f-1, ...]}`` with ``G = L / f``.  Returns ``{"moe": [G, ...],
    "dense": [G, f-1, ...]}`` scan inputs — groups are contiguous runs of
    ``f`` layers (MoE first), so any contiguous slice of the flat attn/norm
    stack aligns with the matching moe/dense group slices, which is what makes
    the layout pipeline-sliceable.
    """
    f = moe_frequency
    g = jax.tree_util.tree_leaves(layer_stack["mlp"]["moe"])[0].shape[0]
    shared = {k: v for k, v in layer_stack.items() if k != "mlp"}
    head = jax.tree_util.tree_map(
        lambda a: a.reshape((g, f) + a.shape[1:])[:, 0], shared)
    tail = jax.tree_util.tree_map(
        lambda a: a.reshape((g, f) + a.shape[1:])[:, 1:], shared)
    return {"moe": {**head, "mlp": layer_stack["mlp"]["moe"]},
            "dense": {**tail, "mlp": layer_stack["mlp"]["dense"]}}


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def _sinkhorn(cost: jax.Array, n_iters: int) -> jax.Array:
    """Sinkhorn normalization of router logits (Megatron top-1 balanced routing,
    reference ``transformer.py:376-467`` RouterSinkhorn)."""
    cost = jnp.exp(cost)
    d0 = jnp.ones(cost.shape[:-1] + (1,), cost.dtype)
    d1 = jnp.ones(cost.shape[-1:], cost.dtype)
    eps = 1e-8
    for _ in range(n_iters):
        d0 = 1.0 / (jnp.sum(d1 * cost, axis=-1, keepdims=True) + eps)
        d1 = 1.0 / (jnp.sum(d0 * cost, axis=-2, keepdims=True).squeeze(-2) / cost.shape[-2] + eps)
    return d0 * cost * d1


def route(router_params, x: jax.Array, cfg: MoEConfig):
    """Token -> expert routing.

    x [tokens, hidden] -> (probs [tokens, k], idx [tokens, k],
    router_logits [tokens, E]).  fp32 throughout.
    """
    logits = x.astype(jnp.float32) @ router_params["w"].astype(jnp.float32)
    if cfg.router_type == "sinkhorn":
        # balanced assignment for selection; gate values from plain softmax
        norm = _sinkhorn(logits, cfg.sinkhorn_iterations)
        _, idx = jax.lax.top_k(norm, cfg.top_k)
        probs_full = jax.nn.softmax(logits, axis=-1)
        probs = jnp.take_along_axis(probs_full, idx, axis=-1)
    else:
        probs_full = jax.nn.softmax(logits, axis=-1)
        probs, idx = jax.lax.top_k(probs_full, cfg.top_k)
    if cfg.normalize_top_k_affinities and cfg.top_k > 1:
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return probs, idx, logits


def load_balancing_loss(router_logits: jax.Array, idx: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Switch/Mixtral aux loss: E * mean_e(frac_tokens_e * frac_prob_e)
    (reference ``load_balancing_loss_func``, ``modeling_mixtral.py:872-878``).
    Unweighted; combine with coefficients via ``weighted_router_loss``."""
    e = cfg.num_experts
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [T, k, E]
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # [E]
    frac_probs = jnp.mean(probs, axis=0)  # [E]
    return e * jnp.sum(frac_tokens * frac_probs) / max(cfg.top_k, 1)


def router_z_loss(router_logits: jax.Array) -> jax.Array:
    """ST-MoE router z-loss: mean(logsumexp(logits)^2) — keeps logits bounded."""
    z = jax.nn.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z**2)


def weighted_router_loss(router_logits: jax.Array, idx: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Per-layer auxiliary loss with coefficients already applied:
    ``aux_coef * load_balancing + z_coef * z``.  Models add the per-layer mean
    of this directly to the LM loss (no further scaling)."""
    loss = cfg.router_aux_loss_coef * load_balancing_loss(router_logits, idx, cfg)
    if cfg.router_z_loss_coef > 0:
        loss = loss + cfg.router_z_loss_coef * router_z_loss(router_logits)
    return loss


# ---------------------------------------------------------------------------
# expert compute
# ---------------------------------------------------------------------------


def _swiglu_experts(expert_params, x_e: jax.Array, compute_dtype) -> jax.Array:
    """Dense per-expert SwiGLU: x_e [E, cap, h] -> [E, cap, h]."""
    gu = jnp.einsum(
        "ech,ehf->ecf", x_e, expert_params["gate_up"].astype(compute_dtype)
    )
    gate, up = jnp.split(gu, 2, axis=-1)
    act = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efh->ech", act, expert_params["down"].astype(compute_dtype))


def moe_dropped(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16):
    """Capacity-factor MoE: tokens over capacity are dropped (pass through 0).

    x [tokens, hidden] -> (y [tokens, hidden], router_logits).
    Dense dispatch/combine einsums (GShard style): static shapes, MXU-friendly,
    and under EP the ``[E, cap, h]`` dispatch tensor all-to-alls over the
    ``expert`` axis automatically.
    """
    t, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = int(max(1, round((cfg.capacity_factor or 1.0) * t * k / e)))
    # inner scopes of "moe": telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("router"):
        probs, idx, logits = route(params["router"], x, cfg)

    with jax.named_scope("dispatch"):
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [T, k, E]
        # position of each (token, k) within its expert's queue
        pos = jnp.cumsum(onehot.reshape(t * k, e), axis=0).reshape(t, k, e) - 1.0
        keep = (pos < cap) * onehot  # drop over-capacity
        pos_cap = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)  # [T,k,E,cap]
        dispatch = jnp.einsum("tke,tkec->tec", keep, pos_cap)  # [T, E, cap] 0/1
        combine = jnp.einsum("tk,tke,tkec->tec", probs.astype(jnp.float32), keep, pos_cap)
        x_e = jnp.einsum("tec,th->ech", dispatch.astype(compute_dtype), x.astype(compute_dtype))
    with jax.named_scope("experts"):
        y_e = _swiglu_experts(params["experts"], x_e, compute_dtype)
    with jax.named_scope("combine"):
        y = jnp.einsum("tec,ech->th", combine.astype(compute_dtype), y_e)
    return y.astype(x.dtype), (probs, idx, logits)


def moe_dropless(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16):
    """Dropless MoE: sort tokens by expert, grouped-matmul via ``lax.ragged_dot``.

    Every token is processed (the reference's ``dropless=True``); group sizes
    are data-dependent but shapes are static ([T*k] rows).
    """
    t, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    # inner scopes of "moe": telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("router"):
        probs, idx, logits = route(params["router"], x, cfg)

    with jax.named_scope("dispatch"):
        flat_expert = idx.reshape(-1)  # [T*k]
        order = jnp.argsort(flat_expert)  # stable sort by expert
        token_of = order // k  # original token index per sorted row
        xs = x.astype(compute_dtype)[token_of]  # [T*k, h] gathered rows
        group_sizes = jnp.bincount(flat_expert, length=e)

    # XLA's SPMD partitioner has no rule for ragged_dot's GROUP dimension:
    # with the expert dim sharded it computes each shard's local expert
    # slice against the GLOBAL group offsets — silently wrong values, no
    # error (full-signal corruption on any mesh where the expert axis is
    # strided, e.g. EP x TP; verified empirically on jax 0.4.x).  Constrain
    # the weights to be gathered over 'expert' for the compute — weight-
    # gather EP: the resident weights and optimizer state stay sharded per
    # expert_specs, GSPMD inserts one all-gather per layer, and the ffn
    # dim's 'model' sharding (which ragged_dot partitions correctly) is
    # preserved.  Sharded-vs-unsharded parity: tests/test_mixtral.py.
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    with jax.named_scope("experts"):
        gu_w = shd.constrain(
            params["experts"]["gate_up"].astype(compute_dtype),
            P(None, None, "model"))
        down_w = shd.constrain(
            params["experts"]["down"].astype(compute_dtype),
            P(None, "model", None))

        gu = jax.lax.ragged_dot(xs, gu_w, group_sizes)
        gate, up = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(gate) * up
        ys = jax.lax.ragged_dot(act, down_w, group_sizes)  # [T*k, h]

    with jax.named_scope("combine"):
        w = probs.reshape(-1)[order].astype(compute_dtype)  # gate weight per row
        y = jnp.zeros((t, h), compute_dtype).at[token_of].add(ys * w[:, None])
    return y.astype(x.dtype), (probs, idx, logits)


def _shuffle_permutation(t: int, group: int) -> jnp.ndarray:
    """Deterministic stride (interleave) permutation of ``t`` tokens.

    The reference's ``token_shuffle_group_size`` (``transformer.py:410-411``)
    randomly shuffles tokens before capacity-factor dispatch so over-capacity
    DROPS are not biased toward late sequence positions (the expert queue
    position is a cumsum in token order).  A fixed stride permutation —
    read the flat token stream as ``[group, t/group]`` column-major — achieves
    the same positional de-correlation deterministically: adjacent sequence
    positions land ``t/group`` apart in the queue.  No PRNG threading, no
    cross-step nondeterminism, exact inverse by transposition.
    """
    g = max(1, min(group, t))
    while t % g:
        g -= 1  # largest divisor <= group (tiny/odd token counts)
    return jnp.arange(t).reshape(t // g, g).T.reshape(-1)


def moe_block(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16):
    """[b, s, h] wrapper dispatching dropped/dropless; returns (y, router_logits)."""
    b, s, h = x.shape
    with jax.named_scope("moe"):
        flat = x.reshape(b * s, h)
        shuffle = (not cfg.dropless) and (cfg.token_shuffle_group_size or 0) > 1
        if shuffle:
            # only the dropped path is order-dependent (queue-position cumsum);
            # dropless processes every token, so shuffling there is a no-op cost
            perm = _shuffle_permutation(b * s, int(cfg.token_shuffle_group_size))
            inv = jnp.argsort(perm)
            flat = flat[perm]
        fn = moe_dropless if cfg.dropless else moe_dropped
        y, (probs, idx, logits) = fn(params, flat, cfg, compute_dtype=compute_dtype)
        if shuffle:
            y, idx, logits = y[inv], idx[inv], logits[inv]
        return y.reshape(b, s, h), {"router_logits": logits, "expert_idx": idx}
