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
- **EP**: expert-major weight tensors are resident with their expert dim
  sharded over the ``expert`` mesh axis (``moe_param_specs``).  The dropless
  block routes on the global tokens (plain GSPMD code: sinkhorn normalises
  over all of them), then is a per-shard computation over the mesh axes that
  shard its tokens (``data``, ``expert``, and ``context`` under cp): each
  shard sorts and multiplies only its own rows, against all experts, whose
  weights it all-gathers over ``expert`` in the compute dtype; the gather's
  transpose reduce-scatters the shards' partial weight gradients in
  ``reduce_dtype`` (weight-gather EP; ``_dropless_on_mesh``).  There is no collective on the
  token path, and no all-to-all: sending each token to its experts' chip
  (the reference's NxD token shuffle) moves fewer bytes and is not built.

SwiGLU experts (``glu_mlp`` in the reference): w_gate/w_up fused as one
``[E, h, 2*ff]`` tensor, w_down ``[E, ff, h]``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.parallel import sharding as shd


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_experts(w: jax.Array, axis: str, compute_dtype) -> jax.Array:
    """All-gather expert-major weights over the manual mesh axis ``axis``, in
    ``compute_dtype``.

    The transpose is the cross-shard sum of the partial weight gradients,
    each shard's from its own rows.  Before the block was per-shard that sum
    happened inside one kernel's float32 accumulator, so it is carried in the
    dtype ``w`` arrives in (``_dropless_on_mesh`` hands it over in
    ``reduce_dtype``) and reduce-scattered straight back to the resident
    layout."""
    return jax.lax.all_gather(w.astype(compute_dtype), axis, axis=0, tiled=True)


def _gather_experts_fwd(w, axis, compute_dtype):
    # the residual is a zero-size carrier of w's dtype
    return _gather_experts(w, axis, compute_dtype), jnp.zeros((0,), w.dtype)


def _gather_experts_bwd(axis, compute_dtype, res, ct):
    return (jax.lax.psum_scatter(
        ct.astype(res.dtype), axis, scatter_dimension=0, tiled=True),)


_gather_experts.defvjp(_gather_experts_fwd, _gather_experts_bwd)


def _gather_experts_on_mesh(w, axis: str, compute_dtype, spec: P):
    """``_gather_experts`` where ``axis`` is manual and ``spec`` lays ``w``
    out over the axes that are still automatic (the ffn dim over ``model``).
    The partitioner has no rule for a collective whose operand is sharded
    over an automatic axis: it would replicate the weights over ``model``
    around the gather, and their gradients around the reduce-scatter.  So the
    two collectives run in a nested region that is manual over ``model`` too,
    each tp rank gathering the ffn slice it owns."""
    auto = frozenset(a for a in jax.tree_util.tree_leaves(tuple(spec)) if a)
    return shd.shard_map(
        functools.partial(_gather_experts, axis=axis, compute_dtype=compute_dtype),
        mesh=jax.sharding.get_abstract_mesh(), in_specs=spec, out_specs=spec,
        axis_names=auto, check_vma=False,
    )(w)


def _dropless_experts(experts, x: jax.Array, probs: jax.Array, idx: jax.Array,
                      cfg: MoEConfig, *, compute_dtype,
                      expert_axis: Optional[str] = None) -> jax.Array:
    """The routed half of the dropless block: sort rows by expert, grouped
    matmul via ``lax.ragged_dot``, weighted scatter-add back.

    x [T, h], probs / idx [T, k] -> y [T, h] in ``compute_dtype``.  A function
    of one token set: given its routing, a row's output depends on the row,
    its experts' weights and its gate weights only, so ``_dropless_on_mesh``
    runs it once per token shard, with ``expert_axis`` the manual mesh axis
    the expert weights arrive sharded over.
    """
    t, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    # inner scopes of "moe": telemetry.spans.DEVICE_SCOPES
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
    # strided, e.g. EP x TP; verified empirically on jax 0.4.x).  So the
    # compute sees every expert — weight-gather EP: the resident weights and
    # optimizer state stay sharded per moe_param_specs, gathered over
    # 'expert' once per layer (by hand where the axis is manual, by the
    # constraint where it is not), and the ffn dim's 'model' sharding (which
    # ragged_dot partitions correctly) is preserved.
    # Sharded-vs-unsharded parity: tests/test_moe.py, tests/test_mixtral.py.
    with jax.named_scope("experts"):
        gu_w, down_w = experts["gate_up"], experts["down"]
        gu_spec, down_spec = P(None, None, "model"), P(None, "model", None)
        if expert_axis is not None:
            gu_w = _gather_experts_on_mesh(gu_w, expert_axis, compute_dtype, gu_spec)
            down_w = _gather_experts_on_mesh(down_w, expert_axis, compute_dtype, down_spec)
        gu_w = shd.constrain(gu_w.astype(compute_dtype), gu_spec)
        down_w = shd.constrain(down_w.astype(compute_dtype), down_spec)

        gu = jax.lax.ragged_dot(xs, gu_w, group_sizes)
        gate, up = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(gate) * up
        ys = jax.lax.ragged_dot(act, down_w, group_sizes)  # [T*k, h]

    with jax.named_scope("combine"):
        w = probs.reshape(-1)[order].astype(compute_dtype)  # gate weight per row
        return jnp.zeros((t, h), compute_dtype).at[token_of].add(ys * w[:, None])


def moe_dropless(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16):
    """Dropless MoE: route, then sort tokens by expert and grouped-matmul via
    ``lax.ragged_dot`` (``_dropless_experts``).

    x [tokens, hidden] -> (y [tokens, hidden], (probs, idx, router_logits)).
    Every token is processed (the reference's ``dropless=True``); group sizes
    are data-dependent but shapes are static ([T*k] rows).
    """
    with jax.named_scope("router"):
        probs, idx, logits = route(params["router"], x, cfg)
    y = _dropless_experts(params["experts"], x, probs, idx, cfg,
                          compute_dtype=compute_dtype)
    return y.astype(x.dtype), (probs, idx, logits)


#: mesh axes that can shard the tokens of a block-boundary activation: the
#: batch over DATA_AXES, the sequence over ``context`` (``shd.act_spec``);
#: ``model`` under SP stays automatic
_TOKEN_AXES = shd.DATA_AXES + ("context",)


def _token_axes(mesh, act_spec: P, b: int, s: int, outer_manual: frozenset):
    """The mesh axes of size > 1 that shard the batch and the sequence
    dimension of a ``[b, s, h]`` activation laid out by ``act_spec``, as two
    tuples; both empty where they do not divide it evenly."""
    dims = []
    for entry, size in zip(tuple(act_spec)[:2], (b, s)):
        names = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in names if a in _TOKEN_AXES
                     and a not in outer_manual and mesh.shape.get(a, 1) > 1)
        if size % math.prod(mesh.shape[a] for a in axes):
            return (), ()
        dims.append(axes)
    return tuple(dims)


def _dropless_on_mesh(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype,
                      reduce_dtype, act_spec: Optional[P]):
    """``moe_dropless`` of ``[b, s, h]``, its expert half partitioned by tokens.

    Routing is not row-local (sinkhorn normalises over the whole token set),
    so it runs first, on the global tokens, under GSPMD.  The routed half
    (``_dropless_experts``) left to GSPMD is replicated: the sort is over the
    global token list and ``ragged_dot`` has no partitioning rule for globally
    sorted rows, so every chip would multiply the whole batch.  Instead it
    runs per shard inside a ``shard_map`` that is manual over the axes
    sharding the tokens (read from the mesh and ``act_spec``) and automatic
    over the rest, as ``ops.attention._flash_on_mesh`` does for the flash
    kernel.  With no mesh, or no such axis, it is called directly.
    Returns ``(y [b, s, h], expert_idx [b*s, k], router_logits [b*s, E])``.
    """
    b, s, h = x.shape
    mesh, outer_manual = shd.region_mesh()
    act_spec = shd.act_spec() if act_spec is None else act_spec
    batch_axes, seq_axes = ((), ()) if mesh is None else _token_axes(
        mesh, act_spec, b, s, outer_manual)
    manual = frozenset(batch_axes + seq_axes)
    shards = math.prod(mesh.shape[a] for a in manual)
    facts = shd.trace_facts()
    if facts is not None:  # 1 anywhere = some block multiplies every row
        facts["moe_token_shards"] = min(shards, facts.get("moe_token_shards", shards))

    tokens = P(batch_axes or None, seq_axes or None, None)
    if manual:
        # one layout for the router and the region: under SP the sequence is
        # gathered over ``model`` once, and routing stays split by batch
        x = shd.constrain(x, tokens)
    flat = x.reshape(b * s, h)
    with jax.named_scope("router"):
        probs, idx, logits = route(params["router"], flat, cfg)
    if not manual:
        y = _dropless_experts(params["experts"], flat, probs, idx, cfg,
                              compute_dtype=compute_dtype)
        return y.reshape(b, s, h).astype(x.dtype), idx, logits

    expert_axis = "expert" if "expert" in manual else None

    def body(experts, x, probs, idx):
        bl, sl, _ = x.shape
        y = _dropless_experts(
            experts, x.reshape(bl * sl, h), probs.reshape(bl * sl, -1),
            idx.reshape(bl * sl, -1), cfg, compute_dtype=compute_dtype,
            expert_axis=expert_axis)
        return y.reshape(bl, sl, h)

    # the cotangent of an input is summed over the manual axes it is
    # replicated over (and, by _gather_experts, over ``expert``) in the
    # input's own dtype: hand the weights over in reduce_dtype
    experts = jax.tree_util.tree_map(
        lambda w: w.astype(reduce_dtype), params["experts"])
    y = shd.shard_map(
        body, mesh=mesh,
        in_specs=({"gate_up": P(expert_axis), "down": P(expert_axis)},
                  tokens, tokens, tokens),
        out_specs=tokens, axis_names=manual, check_vma=False,
    )(experts, x, probs.reshape(b, s, -1), idx.reshape(b, s, -1))
    return y.astype(x.dtype), idx, logits


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


def moe_block(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16,
              reduce_dtype=jnp.float32, act_spec: Optional[P] = None):
    """[b, s, h] wrapper dispatching dropped/dropless; returns (y, router_logits).

    ``act_spec`` is the block-boundary spec ``x`` is laid out by (default
    ``shd.act_spec()``: batch over the data axes); the dropless block is
    partitioned by the token axes it names.  ``reduce_dtype`` carries the
    cross-shard sum of the expert-weight gradients (``policy.reduce_dtype``)."""
    b, s, h = x.shape
    with jax.named_scope("moe"):
        if cfg.dropless:
            y, idx, logits = _dropless_on_mesh(
                params, x, cfg, compute_dtype=compute_dtype,
                reduce_dtype=reduce_dtype, act_spec=act_spec)
            return y, {"router_logits": logits, "expert_idx": idx}
        flat = x.reshape(b * s, h)
        shuffle = (cfg.token_shuffle_group_size or 0) > 1
        if shuffle:
            # the dropped path is order-dependent (queue-position cumsum)
            perm = _shuffle_permutation(b * s, int(cfg.token_shuffle_group_size))
            inv = jnp.argsort(perm)
            flat = flat[perm]
        y, (probs, idx, logits) = moe_dropped(params, flat, cfg, compute_dtype=compute_dtype)
        if shuffle:
            y, idx, logits = y[inv], idx[inv], logits[inv]
        return y.reshape(b, s, h), {"router_logits": logits, "expert_idx": idx}
