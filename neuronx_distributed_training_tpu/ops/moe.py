"""Mixture-of-Experts: routers, expert compute (dropped & dropless), aux losses.

TPU-native re-design of the NxD MoE stack the reference consumes
(``RouterTopK`` / ``RouterSinkhorn`` + ``ExpertMLPs`` + ``MoE`` modules, built at
reference ``modeling_mixtral.py:342-374`` and ``transformer.py:376-467``, with
the dropped-vs-dropless validation at ``training_orchestrator.py:60-102``):

- **router**: top-k softmax routing (Mixtral) or sinkhorn (Megatron top-1)
  over token logits; router always computed in fp32 (routing decisions must
  not flip under bf16).  ``score_func: sigmoid`` (DeepSeek-V3's ``noaux_tc``):
  scores ``sigmoid(logits)``, chosen by ``score + bias`` with a
  ``params["router"]["bias"]`` that takes no gradient, weighed by the scores
  alone; the bias moves by ``bias_update`` after the optimizer
  (``trainer/step.py``), from the per-expert counts the block returns;
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
  shard its tokens (``data``, ``expert``, and ``context`` under cp;
  ``_dropless_on_mesh``).  Over ``expert`` it is an exchange
  (``_exchange_experts``), fixed shapes, no row dropped.  While no chip
  would receive more than twice its fair share of the rows, the rows travel
  and the weights stay: each chip all-gathers its peers' token shards,
  multiplies the rows that chose its resident experts, and the gate-weighted
  outputs are summed back to the tokens' home chips (the reference's NxD
  token shuffle, in collectives XLA:CPU also runs); no collective carries
  an expert weight or its gradient.  Past that (a router that sends most
  rows to one chip's experts makes that chip do most of the work) the
  weights travel: each chip multiplies its own rows against all experts,
  gathered in the compute dtype, their gradients reduce-scattered in
  ``reduce_dtype``.

- **a held range** (``MoEConfig.experts_held``): the program is one chip of
  an expert-parallel deployment, alone.  It routes over all the experts,
  holds a range of them and multiplies the rows that chose one of those
  (``_held_experts``: a sorted-rows operand chosen each step from the count
  of the rows that arrived, ``_HELD_ROWS`` x the even share, more than one
  slice of the wide one past it, no row dropped); the other chips' rows
  are left out and nothing stands in for them.  **A shared expert**
  (``params["shared"]``) is computed once beside the routed sum and added
  ungated; ``routed_scaling_factor`` scales the routed sum against it.

SwiGLU experts (``glu_mlp`` in the reference): w_gate/w_up fused as one
``[E, h, 2*ff]`` tensor, w_down ``[E, ff, h]``.  ``MoEConfig.expert_act:
relu2`` (models/nemotron_h.py): no gate, ``down(relu(up x)^2)``; the leaf
``gate_up`` is then the up matrix alone, ``[E, h, ff]``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm, tgmm as _tgmm
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
    # the renormalised gate weights times this (a routed sum scaled against a
    # shared expert's: models/laguna.py)
    routed_scaling_factor: float = 1.0
    # ``(lo, hi)``: this program holds experts ``lo .. hi - 1`` of
    # ``num_experts`` alone (one chip of an expert-parallel deployment, the
    # others absent): routing runs over all of them, the expert leaves hold
    # ``hi - lo`` and only the rows that chose one of those are multiplied
    # (``_held_experts``); dropless only
    experts_held: Optional[tuple[int, int]] = None
    # "softmax" | "sigmoid": how router logits become scores.  sigmoid: the
    # router holds a ``bias`` besides ``w``, selection is by score + bias,
    # gate weights by the scores alone (over their sum + ``renorm_eps``)
    score_func: str = "softmax"
    # what the sigmoid route adds to the chosen scores' sum before it divides
    # by it: the source's own constant (DeepSeek-V3's 1e-20; models/lfm2.py
    # sets LFM2's 1e-6); no YAML key
    renorm_eps: float = 1e-20
    # the selection bias's step a train step (``bias_update``); 0: never moves
    bias_update_rate: float = 0.0
    # the function of every expert and of the shared expert (``_EXPERT_ACTS``),
    # set by the family; no YAML key
    expert_act: str = "swiglu"

    @property
    def act(self):
        """The experts' function of their first matmul's result."""
        return _EXPERT_ACTS[self.expert_act][0]

    @property
    def experts_resident(self) -> int:
        """The leading dim of the expert leaves."""
        if self.experts_held is None:
            return self.num_experts
        return self.experts_held[1] - self.experts_held[0]

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
            routed_scaling_factor=float(m.get("routed_scaling_factor", 1.0)),
            experts_held=(tuple(int(i) for i in m["experts_held"])
                          if m.get("experts_held") is not None else None),
            # the source's keys (HF deepseek_v3): scoring_func, and
            # topk_method noaux_tc = a selection bias and no auxiliary loss
            score_func=str(m.get("scoring_func", "softmax")),
            bias_update_rate=float(m.get("router_bias_update_rate", 0.0) or 0.0),
        )


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_moe_params(key: jax.Array, hidden: int, ffn: int, cfg: MoEConfig,
                    dtype=jnp.float32, stddev: float = 0.02):
    """Router + expert weights, expert-major ``[E, ...]``: ``gate_up`` the
    fused SwiGLU pair, or the up matrix alone where the experts have no gate
    (``cfg.expert_act``)."""
    kr, kgu, kd = jax.random.split(key, 3)
    e, held = cfg.num_experts, cfg.experts_resident
    up = ffn * _EXPERT_ACTS[cfg.expert_act][1]
    params = {
        "router": {"w": (jax.random.normal(kr, (hidden, e)) * stddev).astype(jnp.float32)},
        "experts": {
            "gate_up": (jax.random.normal(kgu, (held, hidden, up)) * stddev).astype(dtype),
            "down": (jax.random.normal(kd, (held, ffn, hidden)) * stddev).astype(dtype),
        },
    }
    if cfg.score_func == "sigmoid":   # the selection bias: float32 as the router
        params["router"]["bias"] = jnp.zeros((e,))
    return params


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
    if cfg.score_func == "sigmoid":
        # chosen by score + bias, weighed by the score: the bias steers the
        # load and reaches neither the output nor a gradient
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(
            scores + router_params["bias"]), cfg.top_k)
        probs = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.normalize_top_k_affinities:
            probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + cfg.renorm_eps)
        return probs * cfg.routed_scaling_factor, idx, logits
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
    if cfg.routed_scaling_factor != 1.0:
        probs = probs * cfg.routed_scaling_factor
    return probs, idx, logits


def expert_counts(idx: jax.Array, num_experts: int) -> jax.Array:
    """``[E]`` float32: the (token, choice) slots each expert was chosen for."""
    return jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)


def bias_update(bias: jax.Array, counts: jax.Array, rate: float) -> jax.Array:
    """The selection bias after a step (DeepSeek-V3, arXiv:2412.19437,
    2.1.2): up by ``rate`` where an expert was chosen for fewer slots than
    the mean, down where for more; ``counts [..., E]`` over the step's tokens."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - counts).astype(bias.dtype)


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


def _swiglu(gu: jax.Array) -> jax.Array:
    gate, up = jnp.split(gu, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _relu2(up: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(up))


#: ``MoEConfig.expert_act`` -> (the function of the first matmul's result,
#: that result's width over the expert's)
_EXPERT_ACTS = {"swiglu": (_swiglu, 2), "relu2": (_relu2, 1)}


#: XLA's TPU ``ragged-dot`` is on a slow path where a width of the dot is no
#: whole multiple of this, 1.7-2.3 x slower (one v5e, 6 144 real rows of 18 432
#: in 8 groups, K 2688, forward / forward + backward in ms: 1792 wide 2.63 /
#: 8.50, 1856 4.60 / 14.09, 2048 2.04 / 7.28), and the slow path's time
#: follows the rows the groups hold three times as steeply: such widths go
#: through megablox's grouped matmuls instead (Pallas: tiles of rows, each
#: visited once a group it holds; a width that is no whole tile is masked
#: inside the kernel, nothing is padded; the same shape 2.3-2.9 x faster:
#: PERF.md section 6, PR 46)
_RAGGED_COLS = 256

#: (rows, contracted, columns) a tile of ``gmm``, (rows, the result's rows,
#: its columns) of ``tgmm``: the fastest of four tried at 18 432 x 2688 x
#: 1856 and its transposes (PERF.md section 6, PR 46); an operand whose rows
#: are no whole tiles stays with XLA
_GMM_TILES = (256, 2688, 512)
_TGMM_TILES = (512, 896, 1024)


def _tiles(tiles: tuple, rows: int, a: int, b: int) -> Optional[tuple]:
    """The megablox tiling for ``rows`` of widths ``a`` and ``b``, or ``None``
    where the dot is XLA's: by the call's shapes alone."""
    odd = any(w > _RAGGED_COLS and w % _RAGGED_COLS for w in (a, b))
    if not odd or rows % _TGMM_TILES[0]:
        return None
    return (tiles[0], *(min(t, 128 * math.ceil(w / 128)) for t, w in zip(tiles[1:], (a, b))))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"   # jaxlint: disable=JL102


def _rows_dot(xs: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
              transposed: bool = False) -> jax.Array:
    """``[rows, a] x [groups, a, b] -> [rows, b]``, each row against its
    group's matrix (``transposed``: ``w`` is ``[groups, b, a]``).  The rows
    past ``sum(group_sizes)`` are left unwritten by either way."""
    a, b = w.shape[2:0:-1] if transposed else w.shape[1:]
    tiles = _tiles(_GMM_TILES, xs.shape[0], a, b)
    if tiles is None:
        return jax.lax.ragged_dot(xs, w.swapaxes(1, 2) if transposed else w, group_sizes)
    return _gmm(xs, w, group_sizes, preferred_element_type=xs.dtype, tiling=tiles,
                transpose_rhs=transposed, interpret=_interpret())


#: ``lax.ragged_dot``'s transpose for the grouped operand: rows contracted
#: group by group, ``[rows, a] x [rows, b] -> [groups, a, b]``
_WEIGHT_GRAD = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _weights_dot(xs: jax.Array, ys: jax.Array, group_sizes: jax.Array, dtype) -> jax.Array:
    """``[rows, a] x [rows, b] -> [groups, a, b]`` in ``dtype``: the rows
    contracted group by group (``_rows_dot``'s transpose for ``w``)."""
    tiles = _tiles(_TGMM_TILES, xs.shape[0], xs.shape[1], ys.shape[1])
    if tiles is None:
        return jax.lax.ragged_dot_general(xs, ys, group_sizes, _WEIGHT_GRAD,
                                          preferred_element_type=dtype)
    return _tgmm(xs.T, ys, group_sizes, preferred_element_type=dtype, tiling=tiles,
                 interpret=_interpret())


def _dense_experts(expert_params, x_e: jax.Array, compute_dtype, act) -> jax.Array:
    """Dense per-expert MLP: x_e [E, cap, h] -> [E, cap, h]."""
    gu = jnp.einsum(
        "ech,ehf->ecf", x_e, expert_params["gate_up"].astype(compute_dtype)
    )
    return jnp.einsum("ecf,efh->ech", act(gu), expert_params["down"].astype(compute_dtype))


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
        y_e = _dense_experts(params["experts"], x_e, compute_dtype, cfg.act)
    with jax.named_scope("combine"):
        y = jnp.einsum("tec,ech->th", combine.astype(compute_dtype), y_e)
    return y.astype(x.dtype), (probs, idx, logits)


def _expert_rows(x, probs, order, group_sizes, gu_w, down_w, *, k: int,
                 count=None, act):
    """The sorted (token, choice) rows ``order`` of ``x`` through their
    experts (``_rows_dot`` over the groups ``group_sizes`` of ``gu_w`` /
    ``down_w``), weighted by their gate and scatter-added onto ``x``'s tokens.
    Returns ``(y like x, (gu, ys))``: the pre-activations and the expert
    outputs per row, what ``_expert_rows_back`` needs kept.

    ``act``: the experts' function of ``gu`` (``_EXPERT_ACTS``).
    ``count``: where ``order`` can hold more rows than the groups do, the
    number they hold.  XLA's TPU ``ragged-dot`` and the tiled kernels skip
    the rows past ``sum(group_sizes)``, read and leave them unwritten
    (XLA:CPU writes zeros), so they are zeroed going in and coming out."""
    head = functools.partial(_head_rows, count=count)
    # inner scopes of "moe": telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("dispatch"):
        token_of = order // k  # token index per sorted row
        xs = head(x[token_of])  # [rows, h] gathered rows
    with jax.named_scope("experts"):
        gu = _rows_dot(xs, gu_w, group_sizes)
        ys = head(_rows_dot(act(gu), down_w, group_sizes))  # [rows, h]
    with jax.named_scope("combine"):
        w = probs.reshape(-1)[order].astype(x.dtype)  # gate weight per row
        return jnp.zeros_like(x).at[token_of].add(ys * w[:, None]), (gu, ys)


def _head_rows(a: jax.Array, count) -> jax.Array:
    """``a`` with the rows from ``count`` on zeroed (``None``: all of it)."""
    if count is None:
        return a
    return jnp.where((jnp.arange(a.shape[0]) < count)[:, None], a, 0)


def _expert_rows_back(ct, kept, x, probs, order, group_sizes, gu_w, down_w, *,
                      k: int, count=None, grad_dtype, act):
    """The cotangents of ``_expert_rows`` for ``x``, ``probs``, ``gu_w`` and
    ``down_w`` from ``ct``, that of ``y``, and ``kept``; the weights' come
    straight out of the kernel's float32 accumulator in ``grad_dtype``.
    Written out, where autodiff would do: so that ``kept`` is all a caller
    has to hold between the passes, whatever branch it took."""
    gu, ys = kept
    head = functools.partial(_head_rows, count=count)
    with jax.named_scope("combine"):
        token_of = order // k
        ct_rows = ct[token_of]
        w = probs.reshape(-1)[order].astype(x.dtype)
        d_w = jnp.sum(ct_rows * ys, axis=-1, dtype=probs.dtype)
        d_probs = jnp.zeros(probs.size, probs.dtype).at[order].add(d_w).reshape(probs.shape)
        d_ys = head(ct_rows * w[:, None])
    with jax.named_scope("experts"):
        acted, act_back = jax.vjp(act, gu)
        d_down = _weights_dot(acted, d_ys, group_sizes, grad_dtype)
        (d_gu,) = act_back(_rows_dot(d_ys, down_w, group_sizes, transposed=True))
        xs = head(x[token_of])
        d_gate_up = _weights_dot(xs, d_gu, group_sizes, grad_dtype)
        d_xs = head(_rows_dot(d_gu, gu_w, group_sizes, transposed=True))
    with jax.named_scope("dispatch"):
        return jnp.zeros_like(x).at[token_of].add(d_xs), d_probs, d_gate_up, d_down


def _sorted_rows(expert_of: jax.Array, groups: int, rows: int):
    """``(order [rows], group_sizes [groups])`` of the (token, choice) rows
    ``expert_of`` (the group each chose; ``groups`` = none of them): a stable
    sort by group, cut to the first ``rows``."""
    with jax.named_scope("dispatch"):
        return (jnp.argsort(expert_of)[:rows],
                jnp.bincount(expert_of, length=groups + 1)[:groups])


#: the expert rows a chip multiplies when the rows travel to the experts, as
#: a multiple of its fair share ``T * k``.  Past it the experts' weights
#: travel instead (``_exchange_experts``).  XLA's TPU ``ragged-dot`` skips the
#: rows past ``sum(group_sizes)``, but the gathers, the elementwise passes
#: and the scatter around it do not: at Mixtral's widths the expert MLP,
#: forward and backward, on 8 192 rows in 2 groups costs 1.4 % more in an
#: operand of 1.25 x the rows, 7.7 % more in one of 2 x, 21 % more in one of
#: 4 x (one v5e; PERF.md section 6, PR 28).  And a chip that receives ``s``
#: times its share works ``s`` times as long while its peers wait: by 2 x the
#: weights' journey (about 90 ms of a 310 ms step at ep 4) is the cheaper.
_EXCHANGE_ROWS = 2.0


def _on_auto_axes(f, spec: P):
    """``f``, a collective over a manual mesh axis, applied to an array that
    ``spec`` lays out over axes still automatic (an expert weight's ffn dim
    over ``model``).  The partitioner has no rule for a collective whose
    operand is sharded over an automatic axis: it would replicate the
    operand over ``model`` around it.  So ``f`` runs in a nested region that
    is manual over those axes too, each tp rank moving the ffn slice it
    owns."""
    auto = frozenset(a for a in jax.tree_util.tree_leaves(tuple(spec)) if a)
    return shd.shard_map(f, mesh=jax.sharding.get_abstract_mesh(), in_specs=spec,
                         out_specs=spec, axis_names=auto, check_vma=False)


_GATE_UP_SPEC, _DOWN_SPEC = P(None, None, "model"), P(None, "model", None)


def _peers_rows(a: jax.Array, axis: str) -> jax.Array:
    """``[T, ...]`` -> ``[ep * T, ...]``: the rows of every peer over the
    manual mesh axis ``axis``, peer-major."""
    return jax.lax.all_gather(a, axis, axis=0, tiled=True)


def _home_sum(a: jax.Array, axis: str) -> jax.Array:
    """``[ep * T, ...]`` -> ``[T, ...]``: block ``j`` of the rows goes to peer
    ``j``, which sums what arrives in float32.  ``_peers_rows``'s transpose;
    not the reduce-scatter JAX would make of it, which in the rows' own 16
    bits aborts XLA:CPU inside a partly automatic region (the reducer carries
    a sharding there, and ``AllReducePromotion`` cannot clone it).  Four v5e,
    one [4096, 4096] bf16 shard a chip (PERF.md section 6, PR 28): all-gather
    1.65 ms, reduce-scatter 1.73 ms in bf16 and 3.90 in float32, this 1.86."""
    ep = jax.lax.axis_size(axis)
    parts = jax.lax.all_to_all(a.reshape((ep, -1) + a.shape[1:]), axis, 0, 0)
    return jnp.sum(parts, axis=0, dtype=jnp.float32).astype(a.dtype)


def _cast_experts(experts, compute_dtype):
    """``(gate_up, down)`` of ``experts`` in ``compute_dtype``, ffn over ``model``."""
    return (shd.constrain(experts["gate_up"].astype(compute_dtype), _GATE_UP_SPEC),
            shd.constrain(experts["down"].astype(compute_dtype), _DOWN_SPEC))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _exchange_experts(experts, x, probs, chosen, rows_travel, cfg: MoEConfig,
                      bound: int, expert_axis: str, compute_dtype, reduce_dtype):
    """The dropless block's routed half for one token shard of ``ep`` over
    the manual mesh axis ``expert_axis``, each holding ``E / ep`` resident
    experts (``experts``: their weights, as this chip holds them; ``chosen``
    ``[ep * T * k]``: the expert of every (token, choice) row of every peer).

    ``rows_travel`` (the same on every chip): every chip's (token, expert)
    rows go to the chips that hold their experts.  The shards of all peers
    are all-gathered, a chip multiplies the rows that chose its residents (at
    most ``bound``, static) and the gate-weighted outputs are summed back to
    each token's home chip (``_home_sum``).  The weights are read where they
    live and their gradients made there, summed over all rows inside one
    kernel's float32 accumulator: no collective carries either.

    Else the weights travel (a chip that received most of the rows would do
    most of the work, its peers waiting): each chip multiplies its own rows
    against all ``E`` experts, gathered over ``expert_axis`` in
    ``compute_dtype``, and the partial weight gradients, float32 straight
    from the kernel, are reduce-scattered back in ``reduce_dtype``.

    Between the passes both ways keep ``(gu, ys)`` of ``bound`` rows and this
    chip's own weights in ``compute_dtype`` (cast once, ahead of the choice of
    way: what the rows' way multiplies and the weights' way gathers), nothing
    of the gathered weights' shape.  So the weights' way gathers them again in
    its backward pass: one more all-gather of ``E / ep`` experts a chip on a
    step that goes that way (``moe/row_bound`` 1), and no zero-filled room for
    ``E`` experts on one that does not.  ``experts`` may arrive in any float
    dtype (the master's: ``models/mixtral.py``); their gradients leave in it."""
    return _exchange_fwd(experts, x, probs, chosen, rows_travel, cfg, bound,
                         expert_axis, compute_dtype, reduce_dtype)[0]


def _exchange_sides(cfg: MoEConfig, bound: int, axis: str, compute_dtype,
                    reduce_dtype, t: int):
    """``((forward, backward) where the rows travel, (forward, backward)
    where the weights do)`` of ``_exchange_experts``: forward ``(weights,
    experts, x, probs, chosen) -> (y, kept)``, backward ``(ct, kept, weights,
    experts, x, probs, chosen) -> (d_experts, d_x, d_probs)``; ``weights``
    this chip's own (``_cast_experts``), ``experts`` there for their dtype,
    ``kept`` the ``(gu, ys)`` of ``bound`` rows on both sides."""
    e, k = cfg.num_experts, cfg.top_k
    e_local = e // jax.lax.axis_size(axis)
    act = cfg.act

    def gathered(x, probs, chosen):
        """Every peer's tokens, and their rows sorted by this chip's
        residents (every other chip's sort last)."""
        with jax.named_scope("dispatch"):
            x, probs = (_peers_rows(a, axis) for a in (x.astype(compute_dtype), probs))
            chosen = chosen - jax.lax.axis_index(axis) * e_local
            chosen = jnp.where((chosen >= 0) & (chosen < e_local), chosen, e_local)
        order, group_sizes = _sorted_rows(chosen, e_local, bound)
        return x, probs, order, group_sizes, jnp.sum(group_sizes)

    def rows_forward(weights, experts, x, probs, chosen):
        x, probs, order, group_sizes, count = gathered(x, probs, chosen)
        y, kept = _expert_rows(x, probs, order, group_sizes, *weights, k=k, count=count,
                               act=act)
        with jax.named_scope("combine"):
            return _home_sum(y, axis), kept

    def rows_backward(ct, kept, weights, experts, x, probs, chosen):
        x_all, probs_all, order, group_sizes, count = gathered(x, probs, chosen)
        with jax.named_scope("combine"):
            ct = _peers_rows(ct, axis)
        d_x, d_probs, d_gu, d_down = _expert_rows_back(
            ct, kept, x_all, probs_all, order, group_sizes, *weights,
            k=k, count=count, grad_dtype=experts["gate_up"].dtype, act=act)
        with jax.named_scope("dispatch"):
            return ({"gate_up": d_gu, "down": d_down},
                    _home_sum(d_x, axis).astype(x.dtype), _home_sum(d_probs, axis))

    def own(weights, x, chosen):
        """All ``E`` experts' weights gathered beside this chip's, its own
        tokens, and their rows sorted by expert."""
        with jax.named_scope("experts"):
            weights = [_on_auto_axes(functools.partial(_peers_rows, axis=axis), spec)(w)
                       for w, spec in zip(weights, (_GATE_UP_SPEC, _DOWN_SPEC))]
        with jax.named_scope("dispatch"):
            chosen = jax.lax.dynamic_slice_in_dim(
                chosen, jax.lax.axis_index(axis) * t * k, t * k)
        order, group_sizes = _sorted_rows(chosen, e, t * k)
        return weights, x.astype(compute_dtype), order, group_sizes

    def weights_forward(weights, experts, x, probs, chosen):
        weights, xc, order, group_sizes = own(weights, x, chosen)
        y, kept = _expert_rows(xc, probs, order, group_sizes, *weights, k=k, act=act)
        return y, tuple(jnp.pad(a, ((0, bound - t * k), (0, 0))) for a in kept)

    def weights_backward(ct, kept, weights, experts, x, probs, chosen):
        weights, xc, order, group_sizes = own(weights, x, chosen)
        d_x, d_probs, *d_weights = _expert_rows_back(
            ct, [a[:t * k] for a in kept], xc, probs, order, group_sizes,
            *weights, k=k, grad_dtype=reduce_dtype, act=act)
        with jax.named_scope("experts"):
            d_gu, d_down = (
                _on_auto_axes(lambda g: jax.lax.psum_scatter(
                    g, axis, scatter_dimension=0, tiled=True), spec)(g).astype(
                        experts["gate_up"].dtype)
                for g, spec in zip(d_weights, (_GATE_UP_SPEC, _DOWN_SPEC)))
        return {"gate_up": d_gu, "down": d_down}, d_x.astype(x.dtype), d_probs

    return (rows_forward, rows_backward), (weights_forward, weights_backward)


def _exchange_pass(back: int, rows_travel, cfg, bound, expert_axis, compute_dtype,
                   reduce_dtype, *operands):
    """The forward (0) or backward (1) pass of ``_exchange_experts``, the way
    ``rows_travel`` says; ``None``: the bound holds every case."""
    x = operands[-3]  # (..., x, probs, chosen) on either pass
    rows, weights = _exchange_sides(cfg, bound, expert_axis, compute_dtype,
                                    reduce_dtype, x.shape[0])
    if rows_travel is None:
        return rows[back](*operands)
    return jax.lax.cond(rows_travel, rows[back], weights[back], *operands)


def _exchange_fwd(experts, x, probs, chosen, rows_travel, *static):
    # cast ahead of the ``cond`` and kept from here, a result of neither branch
    *_, compute_dtype, _ = static
    operands = (_cast_experts(experts, compute_dtype), experts, x, probs, chosen)
    y, kept = _exchange_pass(0, rows_travel, *static, *operands)
    return y, (kept, *operands, rows_travel)


def _exchange_bwd(*args):
    *static, (kept, *operands, rows_travel), ct = args
    return (*_exchange_pass(1, rows_travel, *static, ct, kept, *operands), None, None)


_exchange_experts.defvjp(_exchange_fwd, _exchange_bwd)


#: the sorted-rows operands of a block that holds a range of the experts
#: (``MoEConfig.experts_held``), narrow and wide, as multiples of the rows it
#: receives when routing is even, ``T * k * held / E``.  What it receives is
#: the data's (one sequence's tokens lean to the same experts: PERF.md section
#: 7), anything up to ``T * min(k, held)``, which as an operand with its
#: ``gu`` would cost the step more memory than the held experts' weights.
#: Every row of an operand is gathered, masked and scattered whether a row
#: arrived for it or not (only the grouped dots skip the rows past the
#: count), so its size is paid on every step it is used: one layer at LFM2's |
#: Kanana's shape (65 536 | 98 304 rows sorted, 8 192 | 12 288 the even
#: share), forward twice and backward once as ``full`` runs it, at a share
#: of 1.05 costs 16.7 | 18.5 ms in one pass over 1.25 x, 17.8 | 20.2 over
#: 1.5 x, 19.9 | 23.3 over 2 x, 23.0 | 27.6 over 3 x (one v5e; PERF.md
#: section 6, PR 52).  So the operand is chosen on the device each step from
#: the count of the rows that arrived.  Up to the narrow multiple: one pass
#: over that operand, ``(gu, ys)`` and the sort kept for the backward.  Past
#: it: the same block on successive slices of the sorted rows, each of the
#: wide multiple, keeping nothing but its inputs and running each slice
#: forward again in the backward pass: no row dropped, no temporary past the
#: wide operand.  A count between the two takes one slice, which costs the
#: layer about what the one pass over 3 x cost it (29.0 | 30.6 ms at a share
#: of 1.7, for 25.9 | 29.9); two slices cost more at a share of 2.5 (45.0 |
#: 48.2, for 29.1 | 32.8) and less at 3.5 (50.3 | 52.4, for the 57.6 | 65.3
#: of two slices of 3 x).  The wide tier is a slice and not a second one pass that
#: keeps its ``(gu, ys)``: a third branch carries its own eight copies of
#: the grouped dots' kernels a layer kind (2.2-2.6 s more of every start to
#: read and load them, 10 % of Kanana's ``setup_s``), and the one pass over
#: 2 x is what makes the compiler rematerialise operations of its own in
#: LFM2's step (9 with it, 1 without: 11 ms a step).  The narrow multiple is
#: the smallest of 1.25 / 1.5 over the 95th percentile of the largest share
#: a step holds in the benchmark's cells: LFM2 1.03, Laguna 1.21-1.30,
#: Nemotron 1.19-1.49 and Kanana 1.32-1.43 by the seed (one step in 190 of
#: either over 1.5); Keye's is 2.5-2.8, half its steps pass 1.5 in some
#: layer, a third of one seed's pass 2.0, and its rate still rises 6.3 %
#: (its other layers fit).  The wide multiple is 2.0:
#: by the one-layer curve 3.0 is cheaper on Keye's steps between 2 and 3
#: (50.3 for 61.9 ms at Keye's shape), dearer on those between 1.5 and 2
#: (45.8 for 38.6) and past 3 (82.2 for 67.8), which its two seeds split
#: evenly, and 2.0 holds the slices' temporaries smaller.
#: Not the exchange's ``_EXCHANGE_ROWS``, though both bound a sorted-rows
#: operand: that one is a chip's share of ``T * k`` rows against the
#: weights' journey (``autotune/cost_model.py`` prices a plan by it), this
#: pair shares of ``T * k * held / E``, one pass against slices.
_HELD_ROWS = (1.5, 2.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _held_experts(experts, x, probs, chosen, k: int, bounds: tuple, compute_dtype, act):
    """The dropless block's routed half where the program holds a range of the
    experts alone: ``experts`` their weights (any float dtype; their
    gradients leave in it, float32 straight from the kernel), ``chosen``
    ``[T * k]`` the held expert of each (token, choice) row, counted from the
    range's first, or ``held`` for a row whose expert lies elsewhere.  Such a
    row is sorted last and left out: nothing stands in for the absent chips.
    ``bounds`` (narrow, wide): rows held at most ``narrow``: one pass over
    that operand, ``(gu, ys)`` and the sort kept; more: slices of ``wide``,
    one where they fit it (``_HELD_ROWS``)."""
    return _held_pass(0, k, bounds, compute_dtype, act, experts, x, probs, chosen)[0]


def _held_sides(k: int, bounds: tuple, compute_dtype, act, held: int, f2: int, h: int):
    """``((forward, backward) of the one pass over the narrow of ``bounds``,
    (forward, backward) by slices of the wide one)`` of ``_held_experts``, in
    ``_exchange_sides``'s shapes."""
    bound, wide = bounds

    def cast(experts):
        return (experts["gate_up"].astype(compute_dtype),
                experts["down"].astype(compute_dtype))

    def grads(x, d_x, d_probs, d_gu, d_down):
        return {"gate_up": d_gu, "down": d_down}, d_x.astype(x.dtype), d_probs

    def under_forward(experts, x, probs, chosen):
        order, sizes = _sorted_rows(chosen, held, bound)
        y, kept = _expert_rows(x.astype(compute_dtype), probs, order, sizes,
                               *cast(experts), k=k, count=jnp.sum(sizes), act=act)
        return y, (*kept, order, sizes)

    def under_backward(ct, kept, experts, x, probs, chosen):
        *kept, order, sizes = kept
        return grads(x, *_expert_rows_back(
            ct, kept, x.astype(compute_dtype), probs, order, sizes, *cast(experts),
            k=k, count=jnp.sum(sizes), grad_dtype=experts["gate_up"].dtype, act=act))

    def slices(chosen):
        """``(n, slice_of)``: the sorted held rows as ``n`` slices of
        ``wide``; ``slice_of(j)`` the rows of slice ``j`` and how many of
        them each group holds."""
        order, sizes = _sorted_rows(chosen, held, chosen.shape[0])
        with jax.named_scope("dispatch"):
            order = jnp.pad(order, (0, -order.shape[0] % wide))
            ends = jnp.cumsum(sizes)

            def slice_of(j):
                lo, hi = j * wide, (j + 1) * wide
                part = jnp.clip(jnp.minimum(ends, hi) - jnp.maximum(ends - sizes, lo), 0)
                return jax.lax.dynamic_slice_in_dim(order, lo, wide), part

            return (ends[-1] + wide - 1) // wide, slice_of

    def past_forward(experts, x, probs, chosen):
        xc, weights = x.astype(compute_dtype), cast(experts)
        n, slice_of = slices(chosen)

        def one(j, y):
            order, sizes = slice_of(j)
            return y + _expert_rows(xc, probs, order, sizes, *weights, k=k,
                                    count=jnp.sum(sizes), act=act)[0]

        # nothing kept: the other side's shapes, empty
        return (jax.lax.fori_loop(0, n, one, jnp.zeros_like(xc)),
                (jnp.zeros((bound, f2), compute_dtype), jnp.zeros((bound, h), compute_dtype),
                 jnp.zeros((bound,), chosen.dtype), jnp.zeros((held,), chosen.dtype)))

    def past_backward(ct, kept, experts, x, probs, chosen):
        xc, weights = x.astype(compute_dtype), cast(experts)
        n, slice_of = slices(chosen)
        grad_dtype = experts["gate_up"].dtype

        def one(j, acc):
            order, sizes = slice_of(j)
            count = jnp.sum(sizes)
            _, kept_j = _expert_rows(xc, probs, order, sizes, *weights, k=k, count=count,
                                     act=act)
            return jax.tree_util.tree_map(jnp.add, acc, _expert_rows_back(
                ct, kept_j, xc, probs, order, sizes, *weights, k=k, count=count,
                grad_dtype=grad_dtype, act=act))

        zero = (jnp.zeros_like(xc), jnp.zeros_like(probs),
                jnp.zeros(experts["gate_up"].shape, grad_dtype),
                jnp.zeros(experts["down"].shape, grad_dtype))
        return grads(x, *jax.lax.fori_loop(0, n, one, zero))

    return (under_forward, under_backward), (past_forward, past_backward)


def _held_pass(back: int, k, bounds, compute_dtype, act, *operands):
    """The forward (0) or backward (1) pass of ``_held_experts``: the one
    pass over the narrow operand or slices of the wide one, by the count of
    the rows held."""
    experts, x, _, chosen = operands[-4:]
    held, narrow = experts["gate_up"].shape[0], bounds[0]
    under, past = _held_sides(k, bounds, compute_dtype, act, held,
                              experts["gate_up"].shape[2], x.shape[1])
    if narrow >= chosen.shape[0]:  # the narrow operand holds every case
        return under[back](*operands)
    return jax.lax.cond(jnp.sum(chosen < held) <= narrow, under[back], past[back],
                        *operands)


def _held_fwd(experts, x, probs, chosen, *static):
    y, kept = _held_pass(0, *static, experts, x, probs, chosen)
    return y, (kept, experts, x, probs, chosen)


def _held_bwd(*args):
    *static, (kept, *operands), ct = args
    return (*_held_pass(1, *static, ct, kept, *operands), None)


_held_experts.defvjp(_held_fwd, _held_bwd)


def _dropless_held(experts, x, probs, idx, cfg: MoEConfig, *, compute_dtype):
    """``_dropless_experts`` under ``cfg.experts_held``; ``stats``:
    ``moe/held_rows``, the rows that chose a held expert,
    ``moe/held_rows_share``, that count over the even share ``T * k * held /
    E``, ``moe/held_operand``, the step's sorted-rows operand over the even
    share (``_HELD_ROWS``: the narrow one where it held the count, else the
    wide one), and ``moe/row_bound``, 1 where the count passed the wide one
    too and the step took more than one slice of it."""
    t, k = idx.shape
    lo, hi = cfg.experts_held
    held = hi - lo
    even = t * k * held / cfg.num_experts
    narrow, wide = bounds = tuple(min(8 * math.ceil(m * even / 8), t * k) for m in _HELD_ROWS)
    with jax.named_scope("dispatch"):
        chosen = idx.reshape(-1) - lo
        chosen = jnp.where((chosen >= 0) & (chosen < held), chosen, held)
        rows = jnp.sum(chosen < held)
    facts = shd.trace_facts()
    if facts is not None:
        facts["moe_experts_held"] = [lo, hi, cfg.num_experts]
        facts["moe_row_bounds"] = list(bounds)
    y = _held_experts(experts, x, probs, chosen, k, bounds, compute_dtype, cfg.act)
    stats = {"moe/held_rows": rows, "moe/held_rows_share": rows / even,
             "moe/held_operand": jnp.where(rows <= narrow, narrow, wide) / even,
             "moe/row_bound": rows > wide}
    return y, jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), stats)


def _dropless_experts(experts, x: jax.Array, probs: jax.Array, idx: jax.Array,
                      cfg: MoEConfig, *, compute_dtype, reduce_dtype=jnp.float32,
                      expert_axis: Optional[str] = None,
                      region_axes: tuple[str, ...] = ()):
    """The routed half of the dropless block: sort rows by expert, grouped
    matmul via ``lax.ragged_dot``, weighted scatter-add back.

    x [T, h], probs / idx [T, k] -> (y [T, h] in ``compute_dtype``, stats).
    A function of one token set: given its routing, a row's output depends on
    the row, its experts' weights and its gate weights only, so
    ``_dropless_on_mesh`` runs it once per token shard.

    With ``expert_axis`` (a manual mesh axis that shards both these tokens
    and the expert dim of ``experts``; ``region_axes``: every manual axis of
    the region) it is ``_exchange_experts``: the rows travel to the chips
    that hold their experts while no chip of the region would receive more
    than ``_EXCHANGE_ROWS`` times its fair share ``T * k``, and past that the
    weights travel.  The count is of the data, max-reduced over the region
    so that every chip goes the same way.  ``stats``:
    ``moe/recv_rows_share_max``, that largest count over the fair share,
    and, where both ways exist, ``moe/row_bound``: 0 where the rows
    travelled, 1 past the bound; empty without ``expert_axis``.
    """
    t, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    if cfg.experts_held is not None:
        if expert_axis is not None:
            raise NotImplementedError(
                "moe.experts_held under the exchange over the expert axis: a "
                "held range is one chip's share, with no peer to exchange with")
        return _dropless_held(experts, x, probs, idx, cfg, compute_dtype=compute_dtype)
    if expert_axis is None:
        # XLA's SPMD partitioner has no rule for ragged_dot's GROUP dimension:
        # with the expert dim sharded it computes each shard's local expert
        # slice against the GLOBAL group offsets — silently wrong values, no
        # error (full-signal corruption on any mesh where the expert axis is
        # strided, e.g. EP x TP; verified empirically on jax 0.4.x).  So the
        # kernel only ever sees a whole expert dim: here all of them,
        # gathered over 'expert' by the constraint (the unpartitioned path a
        # batch the token axes do not divide takes); under ``expert_axis``
        # a chip's residents or a gathered copy, inside the manual region.
        # The ffn dim's 'model' sharding, which ragged_dot partitions
        # correctly, is preserved.
        # Sharded-vs-unsharded parity: tests/test_moe.py, tests/test_mixtral.py.
        weights = _cast_experts(experts, compute_dtype)
        order, group_sizes = _sorted_rows(idx.reshape(-1), e, t * k)
        return _expert_rows(x.astype(compute_dtype), probs, order, group_sizes,
                            *weights, k=k, act=cfg.act)[0], {}

    ep = jax.lax.axis_size(expert_axis)
    e_local = e // ep
    worst = ep * t * min(k, e_local)  # every peer's every token on this chip
    bound = min(math.ceil(_EXCHANGE_ROWS * t * k), worst)
    with jax.named_scope("dispatch"):
        chosen = _peers_rows(idx.reshape(-1), expert_axis)
        received = jax.lax.pmax(jnp.sum(
            chosen // e_local == jax.lax.axis_index(expert_axis)), region_axes)
    stats = {"moe/recv_rows_share_max": received / (t * k)}
    rows_travel = None
    if bound < worst:
        rows_travel = received <= bound
        stats["moe/row_bound"] = 1 - rows_travel
    facts = shd.trace_facts()
    if facts is not None:
        facts["moe_expert_exchange"] = "tokens"
        facts["moe_row_bounds"] = [bound]
    y = _exchange_experts(experts, x, probs, chosen, rows_travel, cfg, bound,
                          expert_axis, compute_dtype, reduce_dtype)
    return y, jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), stats)


def moe_dropless(params, x: jax.Array, cfg: MoEConfig, *, compute_dtype=jnp.bfloat16):
    """Dropless MoE: route, then sort tokens by expert and grouped-matmul via
    ``lax.ragged_dot`` (``_dropless_experts``).

    x [tokens, hidden] -> (y [tokens, hidden], (probs, idx, router_logits)).
    Every token is processed (the reference's ``dropless=True``); group sizes
    are data-dependent but shapes are static ([T*k] rows).
    """
    with jax.named_scope("router"):
        probs, idx, logits = route(params["router"], x, cfg)
    y, _ = _dropless_experts(params["experts"], x, probs, idx, cfg,
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
    kernel.  Where ``expert`` is one of those axes the expert weights enter
    the region as they are resident, ``E / ep`` a chip, and rows or weights
    cross it inside (``_exchange_experts``).  With no mesh, or no such axis,
    it is called directly.  Returns ``(y [b, s, h], expert_idx [b*s, k], router_logits
    [b*s, E], stats)``, ``stats`` the scalars of ``_dropless_experts``.
    """
    b, s, h = x.shape
    mesh, outer_manual = shd.region_mesh()
    act_spec = shd.act_spec() if act_spec is None else act_spec
    batch_axes, seq_axes = ((), ()) if mesh is None else _token_axes(
        mesh, act_spec, b, s, outer_manual)
    manual = batch_axes + seq_axes
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
        y, stats = _dropless_experts(params["experts"], flat, probs, idx, cfg,
                                     compute_dtype=compute_dtype)
        return y.reshape(b, s, h).astype(x.dtype), idx, logits, stats

    expert_axis = "expert" if "expert" in manual else None

    def body(experts, x, probs, idx):
        bl, sl, _ = x.shape
        y, stats = _dropless_experts(
            experts, x.reshape(bl * sl, h), probs.reshape(bl * sl, -1),
            idx.reshape(bl * sl, -1), cfg, compute_dtype=compute_dtype,
            reduce_dtype=reduce_dtype, expert_axis=expert_axis, region_axes=manual)
        return y.reshape(bl, sl, h), stats

    experts = params["experts"]
    if any(a != expert_axis for a in manual):
        # the cotangent of an input is summed over the manual axes it is
        # replicated over (every one but ``expert``) in the input's own
        # dtype: hand the weights over in reduce_dtype
        experts = jax.tree_util.tree_map(lambda w: w.astype(reduce_dtype), experts)
    y, stats = shd.shard_map(
        body, mesh=mesh,
        in_specs=({"gate_up": P(expert_axis), "down": P(expert_axis)},
                  tokens, tokens, tokens),
        out_specs=(tokens, P()), axis_names=frozenset(manual), check_vma=False,
    )(experts, x, probs.reshape(b, s, -1), idx.reshape(b, s, -1))
    return y.astype(x.dtype), idx, logits, stats


def _shared_expert(shared, x: jax.Array, compute_dtype, act=_swiglu) -> jax.Array:
    """An expert every token passes, computed once beside the routed sum and
    added to it ungated (``shared``: ``gate_up`` / ``down`` linears; ``act``
    as the routed experts')."""
    with jax.named_scope("shared"):
        gu = x.astype(compute_dtype) @ shared["gate_up"]["w"].astype(compute_dtype)
        return (act(gu) @ shared["down"]["w"].astype(compute_dtype)).astype(x.dtype)


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
    """[b, s, h] wrapper dispatching dropped/dropless; returns ``(y, aux)``,
    ``aux`` the ``router_logits``, the ``expert_idx`` and ``stats``, per-step
    scalars of the block under their metric names (``_dropless_experts``);
    under ``score_func: sigmoid`` also ``expert_counts [E]``, the slots each
    expert was chosen for, and the stat ``moe/load_max_share``, the largest of
    them over their mean.
    A ``params["shared"]`` is a shared expert, added to the routed sum.

    ``act_spec`` is the block-boundary spec ``x`` is laid out by (default
    ``shd.act_spec()``: batch over the data axes); the dropless block is
    partitioned by the token axes it names.  ``reduce_dtype``
    (``policy.reduce_dtype``) carries the sum of the expert-weight gradients
    over the token axes that replicate the experts, and over ``expert`` where
    the weights travelled (``_exchange_experts``); where the rows did, there
    is none over ``expert``."""
    b, s, h = x.shape
    with jax.named_scope("moe"):
        if cfg.dropless:
            y, idx, logits, stats = _dropless_on_mesh(
                params, x, cfg, compute_dtype=compute_dtype,
                reduce_dtype=reduce_dtype, act_spec=act_spec)
            if "shared" in params:
                y = y + _shared_expert(params["shared"], x, compute_dtype, cfg.act)
            aux = {"router_logits": logits, "expert_idx": idx, "stats": stats}
            if cfg.score_func == "sigmoid":
                # the loads the selection bias answers to (``bias_update``)
                with jax.named_scope("router"):
                    counts = expert_counts(idx, cfg.num_experts)
                aux["expert_counts"] = counts
                aux["stats"] = {**stats, "moe/load_max_share":
                                jnp.max(counts) / (b * s * cfg.top_k / cfg.num_experts)}
            return y, aux
        if cfg.experts_held is not None or "shared" in params:
            raise NotImplementedError(
                "moe.experts_held and a shared expert are wired for the "
                "dropless block only")
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
        return y.reshape(b, s, h), {"router_logits": logits, "expert_idx": idx,
                                    "stats": {}}
