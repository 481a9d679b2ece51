"""Nemotron-H-family decoder (``model_type: nemotron_h``): a stack of layers
that are ONE mixer each, of three kinds.

Every layer is ``x' = x + Mixer(RMS(x))`` (one norm, one residual, no second
half); after the last, ``final_norm`` (the source's ``norm_f``) and an untied
head.  The mixer by the layer's character in ``hybrid_override_pattern``:

- ``M``, **Mamba-2** (kind ``mamba``): ``[z ; xBC ; dt] = u W_in``, ``W_in
  [hidden, d_inner + (d_inner + 2 x n_groups x ssm_state_size) +
  mamba_num_heads]`` with ``d_inner = mamba_num_heads x mamba_head_dim`` (not
  ``expand x hidden``); ``xBC <- silu(conv(xBC) + b)``, a causal depthwise
  convolution of ``conv_kernel`` taps (``ops.short_conv.causal_conv``); ``xBC
  -> x [heads x head_dim], B, C [groups x state]``; the state-space scan
  (``ops.ssd.ssd_scan``: ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log)``,
  ``S_t = exp(dt a) S_{t-1} + dt x B^T``, ``y = S C + D x``, float32 inside);
  the gated norm ``y <- RMS_group(y * silu(z))``, the gate first, then an RMS
  norm inside each of the ``n_groups`` groups of channels with one learned
  scale of ``d_inner``; ``Mixer = y W_out``.
- ``E``, **sparse** (kind ``moe``): ``n_routed_experts`` non-gated experts
  ``down(relu(up u)^2)`` of ``moe_intermediate_size``, ``num_experts_per_tok``
  a token: scores ``sigmoid(u Wr)`` in float32, chosen by ``score + bias``,
  weighed by the scores alone over their sum ``+ 1e-20`` times
  ``routed_scaling_factor`` (``ops.moe.route``), beside one shared expert of
  ``moe_shared_expert_intermediate_size`` every token passes
  (``MoEConfig.expert_act: relu2``: the leaf ``gate_up`` is the up matrix
  alone).  No auxiliary loss.  The source keeps the bias as a buffer; here it
  moves as DeepSeek-V3's does (``ops.moe.bias_update``,
  ``router_bias_update_rate``, the family's ``after_update``), takes no
  gradient and no decay.
- ``*``, **attention** (kind ``attention``): grouped-query attention
  (``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim``)
  with NO position embedding: q and k go to the op as projected
  (``models/llama.py::_attention_block`` with ``cos`` None).
- ``-``, a dense MLP layer of the source's code, is refused by name.

The parameters hold one stack a kind (``layers/mamba``, ``layers/moe``,
``layers/attention``), run by ``models/laguna.py``'s ``stack_plan`` /
``run_stacks``.  A Mamba-2 layer and an attention layer run under the
top-level scope ``attention`` (the token-mixing scope), the Mamba-2 layer
whole under ``attention/mamba`` with ``mamba_conv``, ``ssd_scan`` and
``gated_norm`` inside it; a sparse layer under ``moe``; no layer opens
``mlp`` (``telemetry.spans.FAMILY_SCOPES``).  ``A_log``, ``D`` and
``dt_bias`` lie under ``mixer/head_scales`` (one scalar a head each), stay
float32 under mixed precision and, as the norms and the biases, take no
weight decay.  ``num_experts_held: [lo, hi]`` makes the program one chip of
an expert-parallel deployment, alone (``ops.moe._held_experts``).

``attention_mask`` (left padding) and ``segment_ids`` (packed documents) reach
all three: the convolution sees zeros at padded positions and before a
document's start, the scan's state neither decays nor grows at a padded
position and is reset at a document's start, attention masks the same keys.

Not wired (each refused by name): pipeline stages (a stage would have to
slice every kind's stack), cached decode (three kinds of state: keys and
values, the convolution's last ``conv_kernel - 1`` inputs, the scan's ``S``),
context and sequence parallelism (a shard's first tokens need the previous
shard's ``S`` and its last ``conv_kernel - 1`` inputs: nothing exchanges
them), tensor parallelism, group-limited selection (``n_group`` /
``topk_group`` > 1), biases in the projections, a held range together with
expert parallelism; ``tools/convert.py`` does not know the family's leaves,
and the preference losses' head is not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import AfterUpdate, Family, Refused
from neuronx_distributed_training_tpu.models.laguna import (
    _kind_layers,
    kind_name,
    run_stacks,
    stats_by_kind,
)
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import short_conv as conv_ops
from neuronx_distributed_training_tpu.ops import ssd as ssd_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

#: the pattern's characters -> the layer's kind (its stack's name)
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
#: the loss's aux entry the bias's rule reads: the sparse stack's loads,
#: ``[its layers, experts]``
COUNTS = "moe_expert_counts/moe"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Llama knobs (``llama``: the widths every layer shares, the attention
    layers' heads, fusions, recomputation) + the routed block (``moe``) + the
    Mamba-2 mixer's sizes, under the source's keys."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rescale_prenorm_residual: bool = True

    def sum_tables(self, seq_len: int) -> tuple:
        """The shapes of the tables of ones that a float32 sum is multiplied
        with at ``highest`` at sequences of ``seq_len`` (the running sum
        inside a chunk, ``ops/ssd.py``; a group's mean squares and their way
        back, ``ops/norm.py``): what the graph audit's GA301 knows those dots
        by."""
        inner = self.mamba_num_heads * self.mamba_head_dim
        chunk = min(self.chunk_size, seq_len)
        return ((chunk, chunk), (inner, self.n_groups), (self.n_groups, inner))

    # architecture passthroughs (perf estimation, data-module sizing)
    @property
    def vocab_size(self) -> int:
        return self.llama.vocab_size

    @property
    def hidden_size(self) -> int:
        return self.llama.hidden_size

    @property
    def intermediate_size(self) -> int:
        return self.llama.intermediate_size

    @property
    def num_layers(self) -> int:
        return self.llama.num_layers

    @property
    def num_attention_heads(self) -> int:
        return self.llama.num_attention_heads

    @property
    def num_kv_heads(self):
        return self.llama.num_kv_heads

    @property
    def head_dim(self):
        return self.llama.head_size

    @property
    def family(self) -> Family:
        return FAMILY

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def kinds(self) -> tuple[tuple[str], ...]:
        """The kind of every layer, one part each."""
        return tuple((KINDS[ch],) for ch in self.hybrid_override_pattern)

    def count(self, kind: str) -> int:
        return self.kinds.count((kind,))

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the family is not wired for, each
        # by its key's name
        m, ds = dict(model_cfg or {}), dict(ds_cfg or {})
        base = dataclasses.replace(
            llama.LlamaConfig.from_config(m, ds),
            # the source's spellings, and its untied head
            rms_norm_eps=float(m.get("layer_norm_epsilon", m.get("rms_norm_eps", 1e-5))),
            tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
            initializer_range=float(m.get("initializer_range", 0.02)),
            head_dim=int(m["head_dim"]) if m.get("head_dim") is not None else None)
        # a depth under the source's (a benchmark's or an audit's cut) runs the
        # leading layers: the pattern is read up to it
        n = base.num_layers
        pattern = str(m.get("hybrid_override_pattern") or "M*" * n)[:n]
        if len(pattern) != n:
            raise ValueError(f"model.hybrid_override_pattern has {len(pattern)} layers, "
                             f"the model has {n}")
        if "-" in pattern:
            raise ValueError(
                "model.hybrid_override_pattern: '-' (a dense MLP layer of the nemotron_h "
                "code) is not wired for model.architecture: nemotron_h (wired: M, E, *)")
        if set(pattern) - set(KINDS):
            raise ValueError(f"model.hybrid_override_pattern: unknown "
                             f"{sorted(set(pattern) - set(KINDS))} (known: {sorted(KINDS)})")
        if base.num_attention_heads % base.kv_heads:
            raise ValueError(f"model.num_attention_heads {base.num_attention_heads} is no "
                             f"multiple of num_key_value_heads {base.kv_heads}")
        if base.fuse_qkv is False:
            raise ValueError("model.fuse_qkv: false is not wired for "
                             "model.architecture: nemotron_h")
        for key in ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias"):
            if bool(m.get(key, False)):
                raise ValueError(f"model.{key}: true is not wired for model.architecture: "
                                 "nemotron_h (every projection is without bias)")
        if not bool(m.get("use_conv_bias", True)):
            raise ValueError("model.use_conv_bias: false is not wired for "
                             "model.architecture: nemotron_h (the convolution has a bias)")
        for key, wired in (("mamba_hidden_act", "silu"), ("mlp_hidden_act", "relu2")):
            if str(m.get(key, wired)) != wired:
                raise ValueError(f"model.{key} {m.get(key)!r}: wired for "
                                 f"model.architecture: nemotron_h is {wired}")
        for key in ("n_group", "topk_group"):
            if int(m.get(key, 1) or 1) > 1:
                raise ValueError(f"model.{key} > 1: group-limited selection is not wired "
                                 "for model.architecture: nemotron_h")
        heads, groups = int(m.get("mamba_num_heads", 64)), int(m.get("n_groups", 8))
        if heads % groups:
            raise ValueError(f"model.mamba_num_heads {heads} is no multiple of "
                             f"n_groups {groups}")
        taps = int(m.get("conv_kernel", 4))
        carried = (f"a shard's first tokens need the previous shard's state S of every "
                   f"Mamba-2 layer and its last {taps - 1} convolution inputs, which "
                   "nothing exchanges")
        for key, why in (
                ("pipeline_model_parallel_size",
                 "a stage would have to slice every kind's stack "
                 "(parallel/pipeline.py slices one)"),
                ("tensor_model_parallel_size",
                 "the mixer's heads, groups and channels are not laid out over the "
                 "model axis"),
                ("context_parallel_size", carried)):
            if int(ds.get(key, 1) or 1) > 1:
                raise ValueError(f"distributed_strategy.{key} > 1 is not wired for "
                                 f"model.architecture: nemotron_h: {why}")
        if bool(ds.get("sequence_parallel", False)):
            raise ValueError("distributed_strategy.sequence_parallel is not wired for "
                             f"model.architecture: nemotron_h: {carried}")
        experts = int(m.get("n_routed_experts", 0) or 0)
        if "E" in pattern and experts < 2:
            raise ValueError("model.hybrid_override_pattern has sparse layers (E) and "
                             f"model.n_routed_experts is {experts}")
        if int(m.get("n_shared_experts", 1) or 0) > 1:
            raise ValueError("model.n_shared_experts > 1 is not wired for "
                             "model.architecture: nemotron_h (one shared expert of "
                             "moe_shared_expert_intermediate_size)")
        held = m.get("num_experts_held")
        if held is not None and int(ds.get("expert_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "model.num_experts_held with distributed_strategy."
                "expert_model_parallel_size > 1: a held range is one chip's share "
                "of the experts, with no peer to exchange with (ops/moe.py)")
        if held is not None and not 0 <= int(held[0]) < int(held[1]) <= experts:
            raise ValueError(f"model.num_experts_held {held}: want 0 <= lo < hi <= "
                             f"n_routed_experts {experts}")
        rate = float(m.get("router_bias_update_rate") or 0.0)
        if "E" in pattern and rate <= 0.0:
            raise ValueError(
                f"model.router_bias_update_rate {m.get('router_bias_update_rate')!r}: "
                "the selection bias moves by this step after every optimizer step "
                "(the source keeps it as a buffer; the rule is DeepSeek-V3's, its "
                "gamma 0.001); a bias that never moves is a router without the mechanism")
        moe = dataclasses.replace(moe_ops.MoEConfig.from_config({
            "num_experts": experts or 1, "top_k": int(m.get("num_experts_per_tok", 1)),
            "dropless": True, "router_aux_loss_coef": 0.0,
            "normalize_top_k_affinities": bool(m.get("norm_topk_prob", True)),
            "routed_scaling_factor": float(m.get("routed_scaling_factor", 1.0)),
            "experts_held": held, "scoring_func": "sigmoid",
            "router_bias_update_rate": rate,
        }), expert_act="relu2")
        shared = int(m.get("moe_shared_expert_intermediate_size", 0) or 0)
        return cls(
            llama=base, moe=moe, hybrid_override_pattern=pattern,
            mamba_num_heads=heads, mamba_head_dim=int(m.get("mamba_head_dim", 64)),
            ssm_state_size=int(m.get("ssm_state_size", 128)), n_groups=groups,
            conv_kernel=taps, chunk_size=int(m.get("chunk_size", 128)),
            moe_intermediate_size=int(m.get("moe_intermediate_size", 1856)),
            moe_shared_expert_intermediate_size=(
                shared if int(m.get("n_shared_experts", 1) or 0) else 0),
            time_step_min=float(m.get("time_step_min", 0.001)),
            time_step_max=float(m.get("time_step_max", 0.1)),
            time_step_floor=float(m.get("time_step_floor", 1e-4)),
            rescale_prenorm_residual=bool(m.get("rescale_prenorm_residual", True)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: NemotronHConfig, kind: str, dtype):
    """One layer of ``kind`` (unstacked)."""
    lc = cfg.llama
    ks = jax.random.split(key, 8)
    h, std = lc.hidden_size, lc.initializer_range

    def linear(k, n_in, n_out, scale=1.0):
        return linear_ops.init_linear(k, n_in, n_out, shard="replicated", dtype=dtype,
                                      stddev=std * scale)[0]

    params: dict[str, Any] = {"norm": norm_ops.init_rms_norm(h, dtype=dtype)[0]}
    if kind == "mamba":
        heads, d_inner = cfg.mamba_num_heads, cfg.d_inner
        # the source's draws: A = 1..heads, D = 1, dt log-uniform in
        # [time_step_min, time_step_max] floored at time_step_floor and stored
        # as its inverse softplus; out_proj / sqrt(layers) under
        # rescale_prenorm_residual
        step = jnp.exp(jax.random.uniform(ks[2], (heads,)) * (
            math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
            + math.log(cfg.time_step_min))
        step = jnp.maximum(step, cfg.time_step_floor)
        rescale = 1.0 / math.sqrt(lc.num_layers) if cfg.rescale_prenorm_residual else 1.0
        params["mixer"] = {
            "in_proj": linear(ks[0], h, d_inner + cfg.conv_dim + heads),
            # the taps drawn as the linears, ``[taps, channels]``
            "conv": {**linear(ks[1], cfg.conv_kernel, cfg.conv_dim),
                     "bias": jnp.zeros((cfg.conv_dim,), dtype)},
            "head_scales": {
                "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                "D": jnp.ones((heads,), jnp.float32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step))},
            "gated_norm": norm_ops.init_rms_norm(d_inner, dtype=dtype)[0],
            "out_proj": linear(ks[3], d_inner, h, rescale)}
    elif kind == "attention":
        d, nh, nkv = lc.head_size, lc.num_attention_heads, lc.kv_heads
        params["attn"] = {"qkv": linear(ks[0], h, (nh + 2 * nkv) * d),
                          "o": linear(ks[3], nh * d, h)}
    else:
        params["mlp"] = moe_ops.init_moe_params(
            ks[7], h, cfg.moe_intermediate_size, cfg.moe, dtype=dtype, stddev=std)
        if cfg.moe_shared_expert_intermediate_size:
            width = cfg.moe_shared_expert_intermediate_size
            params["mlp"]["shared"] = {"gate_up": linear(ks[4], h, width),
                                       "down": linear(ks[5], width, h)}
    return params


def init_params(key: jax.Array, cfg: NemotronHConfig, policy: DtypePolicy | None = None):
    """The parameter pytree: ``embed``, ``layers`` one stack per kind
    (``layers/mamba``, ``layers/moe``, ``layers/attention``), each in layer
    order, layer ``i`` drawn from the ``i``-th of the layers' keys,
    ``final_norm`` and the untied ``lm_head``."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    kemb, klayers, khead = jax.random.split(key, 3)
    layer_keys = jax.random.split(klayers, lc.num_layers)
    params: dict[str, Any] = {
        "embed": linear_ops.init_embedding(
            kemb, lc.vocab_size, lc.hidden_size, dtype=dtype, stddev=lc.initializer_range)[0],
        "layers": {
            kind_name(*kind): jax.vmap(
                lambda k, kind=kind: _init_layer(k, cfg, kind[0], dtype))(
                    layer_keys[jnp.asarray(indices)])
            for kind, indices in _kind_layers(cfg).items()},
        "final_norm": norm_ops.init_rms_norm(lc.hidden_size, dtype=dtype)[0],
    }
    if not lc.tie_word_embeddings:
        params["lm_head"], _ = linear_ops.init_linear(
            khead, lc.hidden_size, lc.vocab_size, shard="column", dtype=dtype,
            stddev=lc.initializer_range)
    return params


def param_specs(cfg: NemotronHConfig, *, pipeline: bool = False):
    """PartitionSpec tree of ``init_params``: the vocabulary over ``model`` as
    llama's; the layers replicated but for the expert dim over ``expert``
    where the experts are all held (tp is refused at the config)."""
    if pipeline:
        raise NotImplementedError(FAMILY.pipeline.sentence)
    w2 = {"w": P(None, None, None)}   # every leaf leads with its stack's layers
    vec = P(None, None)
    w3 = P(None, None if cfg.moe.experts_held is not None else "expert", None, None)

    def layer(kind):
        specs: dict[str, Any] = {"norm": {"scale": vec}}
        if kind == "mamba":
            specs["mixer"] = {
                "in_proj": w2, "conv": {**w2, "bias": vec},
                "head_scales": {"A_log": vec, "D": vec, "dt_bias": vec},
                "gated_norm": {"scale": vec}, "out_proj": w2}
        elif kind == "attention":
            specs["attn"] = {"qkv": w2, "o": w2}
        else:
            specs["mlp"] = {"router": {**w2, "bias": vec},
                            "experts": {"gate_up": w3, "down": w3}}
            if cfg.moe_shared_expert_intermediate_size:
                specs["mlp"]["shared"] = {"gate_up": w2, "down": w2}
        return specs

    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": {kind_name(*kind): layer(kind[0]) for kind in _kind_layers(cfg)},
        "final_norm": {"scale": P(None)},
    }
    if not cfg.llama.tie_word_embeddings:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _cast_layer(lp, policy: DtypePolicy):
    """The per-layer cast to the compute dtype of all but what decides or
    integrates in float32: the router and the expert weights (as
    models/laguna.py), and the scan's per-head scalars."""
    cast = policy.cast_to_compute(lp)
    if "mixer" in lp:
        return {**cast, "mixer": {**cast["mixer"],
                                  "head_scales": lp["mixer"]["head_scales"]}}
    if "mlp" in lp:
        return {**cast, "mlp": {**cast["mlp"], "experts": lp["mlp"]["experts"],
                                "router": lp["mlp"]["router"]}}
    return cast


def _mamba_block(lp, u, cfg: NemotronHConfig, attention_mask=None, segment_ids=None):
    """``u`` (already normed) through ``in_proj``, the convolution, the scan,
    the gated norm and ``out_proj``."""
    b, s, _ = u.shape
    heads, p, n, g = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
                      cfg.n_groups)
    d_inner = cfg.d_inner
    z, xbc, dt = jnp.split(linear_ops.apply_linear(lp["in_proj"], u),
                           [d_inner, d_inner + cfg.conv_dim], axis=-1)
    with jax.named_scope("mamba_conv"):
        xbc = conv_ops.causal_conv(xbc, lp["conv"]["w"], lp["conv"]["bias"], silu=True,
                                   attention_mask=attention_mask, segment_ids=segment_ids)
    x, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    scales = lp["head_scales"]
    with jax.named_scope("ssd_scan"):
        y = ssd_ops.ssd_scan(
            x.reshape(b, s, heads, p), bmat.reshape(b, s, g, n), cmat.reshape(b, s, g, n),
            dt, scales["A_log"], scales["D"], scales["dt_bias"], chunk=cfg.chunk_size,
            attention_mask=attention_mask, segment_ids=segment_ids)
    with jax.named_scope("gated_norm"):
        y = norm_ops.apply_gated_rms_norm(lp["gated_norm"], y.reshape(b, s, d_inner), z,
                                          groups=g, eps=cfg.llama.rms_norm_eps)
    return linear_ops.apply_linear(lp["out_proj"], y)


def _decoder_layer(lp, x, cfg: NemotronHConfig, policy: DtypePolicy, kind: str,
                   attention_mask=None, segment_ids=None):
    """One layer of ``kind`` -> ``(x, stats)``; ``stats`` the routed block's
    per-step values (``ops.moe.moe_block``) and its experts' loads under
    ``COUNTS``, none in the other kinds."""
    lc = cfg.llama
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES (attention: the token-mixing
    # scope, whichever mixer fills it) and FAMILY_SCOPES
    if kind == "mamba":
        with jax.named_scope("attention"), jax.named_scope("mamba"):
            hidden = norm_ops.apply_rms_norm(lp["norm"], x, eps=lc.rms_norm_eps)
            hidden = _mamba_block(lp["mixer"], hidden, cfg, attention_mask=attention_mask,
                                  segment_ids=segment_ids)
            return shd.constrain(x + hidden, aspec), {}
    if kind == "attention":
        with jax.named_scope("attention"):
            hidden = norm_ops.apply_rms_norm(lp["norm"], x, eps=lc.rms_norm_eps)
            hidden = llama._attention_block(
                lp["attn"], hidden, None, None, lc, policy, attention_mask=attention_mask,
                segment_ids=segment_ids, sliding_window=None)
            return shd.constrain(x + hidden, aspec), {}
    # moe_block opens the "moe" scope itself; the norm before it and the
    # residual after it belong with it (as models/mixtral.py)
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec)
    with jax.named_scope("moe"):
        x = shd.constrain(x + hidden, aspec)
        stats = {**aux["stats"], COUNTS: aux["expert_counts"],
                 "moe/bias_abs_max": jnp.max(jnp.abs(lp["mlp"]["router"]["bias"]))}
    return x, stats


def decoder_stack(layers, x, cfg: NemotronHConfig, policy: DtypePolicy, *,
                  attention_mask=None, segment_ids=None):
    """The whole stack by ``stack_plan`` -> ``(x, the sparse layers' stats,
    each ``[the kind's layers, ...]``)``.  A run of one layer stays merged
    with its rerun (as models/laguna.py)."""
    no_flash = dataclasses.replace(cfg.llama, attention_impl="core")

    def run_of(kind):
        def body(x, lp):
            return _decoder_layer(_cast_layer(lp, policy), x, cfg, policy, kind[0],
                                  attention_mask=attention_mask, segment_ids=segment_ids)
        # only an attention layer holds a flash kernel: the other stacks'
        # ``remat`` entries say nothing of one
        body = llama.checkpoint_layer(
            body, cfg.llama if kind[0] == "attention" else no_flash,
            stack=kind_name(*kind))
        return lambda x, stack: jax.lax.scan(body, x, stack)

    x, all_stats = run_stacks(layers, x, cfg.kinds, run_of)
    return x, stats_by_kind(cfg.kinds, all_stats).get(("moe",), {})


def forward(params, batch: dict[str, jax.Array], cfg: NemotronHConfig, policy: DtypePolicy,
            *, shift_labels: bool = True, return_logits: bool = False):
    """Causal-LM forward -> ``(loss, aux)``: llama's loss and nothing beside
    it (no auxiliary loss); ``aux`` carries the experts' loads."""
    lc = cfg.llama
    attention_mask, segment_ids = batch.get("attention_mask"), batch.get("segment_ids")
    x = linear_ops.apply_embedding(
        params["embed"], batch["input_ids"], compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, shd.act_spec(lc.sequence_parallel, lc.context_parallel))
    x, stats = decoder_stack(params["layers"], x, cfg, policy,
                             attention_mask=attention_mask, segment_ids=segment_ids)
    aux: dict[str, Any] = {}
    if stats:
        aux[COUNTS] = stats.pop(COUNTS)
        # the expert blocks' scalars (moe/...), the largest over the layers
        aux.update({name: jnp.max(value) for name, value in sorted(stats.items())})
    with jax.named_scope("ce_head"):
        hidden = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
        out, head_aux = llama._head_loss(params, hidden, batch, lc, policy,
                                         shift_labels=shift_labels,
                                         return_logits=return_logits)
    aux.update(head_aux)
    if batch.get("labels") is not None:
        aux["lm_loss"] = out
    return out, aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def ssd_flops_per_token(cfg: NemotronHConfig) -> float:
    """The recurrence's own forward FLOPs a token a layer: a multiply-add for
    ``dt x B^T`` into ``S``, one for the decay of ``S``, one for ``S C``, a
    head's ``head_dim x state`` entries each."""
    return 6.0 * cfg.d_inner * cfg.ssm_state_size


def flops_breakdown(cfg: NemotronHConfig, seq_len: int) -> dict[str, float]:
    """``Family.flops_breakdown``: under ``attention`` every token mixer (a
    Mamba-2 layer's two projections, its taps and its scan; an attention
    layer's projections and causal scores); under ``mlp`` the shared expert
    and, of the routed experts, only the slots this program holds (the
    expected ``top_k * held / E`` a token)."""
    lc = cfg.llama
    h, d, nh, nkv = lc.hidden_size, lc.head_size, lc.num_attention_heads, lc.kv_heads
    mamba = (2 * h * (cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads)
             + 2 * cfg.d_inner * h + 2 * cfg.conv_kernel * cfg.conv_dim
             + ssd_flops_per_token(cfg))
    attention = (2 * h * (nh + 2 * nkv) * d + 2 * nh * d * h
                 + 4 * nh * d * (seq_len + 1) / 2)
    n_sparse = cfg.count("moe")
    slots = cfg.moe.top_k * cfg.moe.experts_resident / cfg.moe.num_experts
    return {
        "attention": float(cfg.count("mamba") * mamba + cfg.count("attention") * attention),
        "mlp": float(n_sparse * 4 * h * (cfg.moe_intermediate_size * slots
                                         + cfg.moe_shared_expert_intermediate_size)),
        "router": float(n_sparse * 2 * h * cfg.moe.num_experts),
        "head": 2.0 * h * lc.vocab_size,
    }


def _run_facts(cfg: NemotronHConfig, sched) -> dict:
    facts: dict[str, Any] = {
        "layer_kinds": {kind: cfg.count(kind) for kind in KINDS.values()},
        "attention_positions": "none",
        "ssd": {"heads": cfg.mamba_num_heads, "head_dim": cfg.mamba_head_dim,
                "state": cfg.ssm_state_size, "groups": cfg.n_groups,
                "chunk": cfg.chunk_size, "way": ssd_ops.WAY,
                "bytes_per_token": ssd_ops.bytes_per_token(
                    cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
                    cfg.n_groups)},
        "mamba_conv": {"taps": cfg.conv_kernel, "channels": cfg.conv_dim,
                       "way": conv_ops.CONV_WAY}}
    if cfg.count("moe"):
        facts["moe_expert_act"] = cfg.moe.expert_act
        facts["moe_score_func"] = cfg.moe.score_func
        if cfg.moe.experts_held is not None:
            facts["moe_experts_held"] = [*cfg.moe.experts_held, cfg.moe.num_experts]
    return facts


def _after_update(cfg: NemotronHConfig) -> Optional[AfterUpdate]:
    """The selection bias's rule: every sparse layer's ``router/bias`` moves
    by the loads its experts met in the step's tokens."""
    if not cfg.count("moe"):
        return None

    def apply(params, aux):
        stack = params["layers"]["moe"]
        router = stack["mlp"]["router"]
        moved = moe_ops.bias_update(router["bias"], aux[COUNTS], cfg.moe.bias_update_rate)
        return {**params, "layers": {**params["layers"], "moe": {
            **stack, "mlp": {**stack["mlp"], "router": {**router, "bias": moved}}}}}

    return AfterUpdate(reads=(COUNTS,), apply=apply)


def _logits(cfg: NemotronHConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, _ = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, 0.0

    return fwd


FAMILY = Family(
    name="nemotron_h",
    config_from=NemotronHConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    # llama's layout with the attention layers' heads: the planner prices
    # neither the Mamba-2 mixer nor the experts
    plan_shape=lambda cfg: llama.plan_shape(cfg.llama),
    logits=_logits,
    head=Refused(
        "model.architecture: nemotron_h has no head for the preference losses: "
        "llama.head is not wired to a stack whose layers are one mixer each"),
    pipeline=Refused(
        "pipeline parallelism not wired for NemotronHConfig (nemotron_h): a stage "
        "would have to slice every kind's stack (parallel/pipeline.py slices one) and "
        "hand on nothing but activations, as it does"),
    onef1b_head=Refused(
        "NemotronHConfig (nemotron_h): head not wired for the manual-vjp schedules "
        "(supported families: llama/mistral)"),
    decode=Refused(
        "model.architecture: nemotron_h has no cached decode: three kinds of state, "
        "an attention layer's keys and values, a Mamba-2 layer's last conv_kernel - 1 "
        "convolution inputs and its scan's state S (models/decode.py holds one kind "
        "of cache)"),
    run_facts=_run_facts,
    after_update=_after_update,
)
