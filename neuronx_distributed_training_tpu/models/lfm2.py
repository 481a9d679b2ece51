"""LFM2-MoE-family decoder (``model_type: lfm2_moe``): a stack whose token
mixing is a gated short convolution in most layers and attention in the rest.

Every layer is ``h = x + Op(RMS(x))`` (``operator_norm``) then ``x' = h +
F(RMS(h))`` (``ffn_norm``); after the last, ``embedding_norm`` and the head,
tied to the embedding.  What fills the two slots, by the source's keys:

- ``layer_types[l]``: ``conv``, the gated short convolution: ``[B ; C ; z] =
  u W_in`` (``[hidden, 3 x hidden]``), ``g = B * z``, a depthwise causal
  convolution of ``conv_L_cache`` taps over ``g`` (zero before the sequence's
  start), ``y = C * c``, ``Op = y W_out`` (``ops/short_conv.py`` has the
  middle; no activation function anywhere in it); or ``full_attention``:
  grouped-query attention (``num_attention_heads`` / ``num_key_value_heads``,
  heads of ``hidden_size / num_attention_heads``: 64 at the published sizes,
  half a lane width, which the flash kernels take as they are) with an RMS
  norm on every head's q and k before the rope, one learned scale of
  ``head_dim`` for all the query heads and one for the key heads
  (``models/llama.py::_attention_block``'s ``q_norm`` / ``k_norm`` leaves);
  rope on all the head's dims in rotate-half pairs.
- the first ``num_dense_layers`` layers a SwiGLU of ``intermediate_size``;
  the others ``num_experts`` SwiGLU experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token: scores ``sigmoid(h Wr)`` in float32, chosen
  by ``score + bias`` (``use_expert_bias``), weighed by the scores alone over
  their sum ``+ 1e-6`` times ``routed_scaling_factor`` (``ops.moe.route``).
  No shared expert, no auxiliary loss.  The source keeps the bias as a buffer
  and publishes no rule for it; here it moves as DeepSeek-V3's does
  (``ops.moe.bias_update``, ``router_bias_update_rate``, the family's
  ``after_update``), takes no gradient and no decay.

The two choices vary independently, so a layer's kind is ``(operator, ffn)``
and the parameters hold one stack a kind (``layers/conv_dense``,
``layers/full_sparse``, ...: ``models/laguna.py::kind_name``), run by that
module's ``stack_plan`` / ``run_stacks``: at the published 40 layers a run of
2, 9 periods of 1 + 3, a run of 1 and 1.  Both operators run under the
top-level scope ``attention`` (the token-mixing half of a layer), the
convolution's half under ``attention/short_conv`` with its middle under
``conv_gate``, the head norms under ``qk_norm``
(``telemetry.spans.FAMILY_SCOPES``).  ``num_experts_held: [lo, hi]`` makes the
program one chip of an expert-parallel deployment, alone
(``ops.moe._held_experts``).

``attention_mask`` (left padding) and ``segment_ids`` (packed documents) reach
both operators: the convolution sees zeros at padded positions and before a
document's start, attention masks the same keys.

Not wired (each refused by name): pipeline stages (a stage would have to
slice every kind's stack), cached decode (two kinds of state: keys and
values, and the convolution's last ``conv_L_cache - 1`` inputs), context and
sequence parallelism (a shard's first tokens need the previous shard's last
``conv_L_cache - 1`` gate products: a halo nothing exchanges), tensor
parallelism, a convolution bias, a router without its bias, a held range
together with expert parallelism; ``tools/convert.py`` does not know the
family's leaves, and the preference losses' head is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import AfterUpdate, Family, Refused
from neuronx_distributed_training_tpu.models.laguna import (
    _cast_layer,
    _kind_layers,
    kind_name,
    run_stacks,
    stats_by_kind,
)
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.ops import short_conv as conv_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

OPERATOR_TYPES = ("conv", "full_attention")
#: the loss's aux entries the bias's rule reads, one a sparse kind:
#: ``COUNTS/<kind's stack>`` ``[the kind's layers, experts]``
COUNTS = "moe_expert_counts"
#: what the sigmoid route adds to the chosen scores' sum (the source's code)
RENORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Llama knobs (``llama``: the widths every layer shares, the dense MLP's
    ``intermediate_size``, fusions, recomputation) + the routed block
    (``moe``) + what differs by layer."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    layer_types: tuple[str, ...] = ()
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    moe_intermediate_size: int = 1536

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
    def sparse(self) -> bool:
        return self.moe.num_experts > 1

    @property
    def ffn_types(self) -> tuple[str, ...]:
        """The dense layers lead."""
        return tuple("sparse" if self.sparse and i >= self.num_dense_layers else "dense"
                     for i in range(self.num_layers))

    @property
    def kinds(self) -> tuple[tuple[str, str], ...]:
        """``(operator, ffn)`` of every layer."""
        return tuple(zip(self.layer_types, self.ffn_types))

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the family is not wired for, each
        # by its key's name
        m, ds = dict(model_cfg or {}), dict(ds_cfg or {})
        rope = dict(m.get("rope_parameters") or {})
        if str(rope.get("rope_type", "default")) != "default":
            raise ValueError(f"model.rope_parameters.rope_type {rope.get('rope_type')!r}: "
                             "wired for model.architecture: lfm2 is default")
        base = dataclasses.replace(
            llama.LlamaConfig.from_config(m, ds),
            # the source's spellings, and its convention of a tied head
            rms_norm_eps=float(m.get("norm_eps", m.get("rms_norm_eps", 1e-5))),
            rope_theta=float(rope.get("rope_theta", m.get("rope_theta", 1e6))),
            tie_word_embeddings=bool(m.get("tie_word_embeddings", True)),
            head_dim=int(m["head_dim"]) if m.get("head_dim") is not None else None)
        # a depth under the source's (a benchmark's or an audit's cut) runs the
        # leading layers: the list is read up to it
        n = base.num_layers
        layer_types = tuple(m.get("layer_types") or ("conv",) * n)[:n]
        if len(layer_types) != n:
            raise ValueError(f"model.layer_types lists {len(layer_types)} layers, "
                             f"the model has {n}")
        if set(layer_types) - set(OPERATOR_TYPES):
            raise ValueError(f"model.layer_types: unknown "
                             f"{sorted(set(layer_types) - set(OPERATOR_TYPES))} "
                             f"(known: {OPERATOR_TYPES})")
        if base.num_attention_heads % base.kv_heads:
            raise ValueError(f"model.num_attention_heads {base.num_attention_heads} is no "
                             f"multiple of num_key_value_heads {base.kv_heads}")
        if bool(m.get("conv_bias", False)):
            raise ValueError("model.conv_bias: true is not wired for model.architecture: "
                             "lfm2 (the filter and both projections are without bias)")
        taps = int(m.get("conv_L_cache", 3))
        if taps < 1:
            raise ValueError(f"model.conv_L_cache {taps}: want at least one tap")
        if base.fuse_qkv is False:
            raise ValueError("model.fuse_qkv: false is not wired for "
                             "model.architecture: lfm2")
        halo = (f"a shard's first tokens need the previous shard's last {taps - 1} gate "
                "products of every convolution layer, a halo nothing exchanges")
        for key, why in (
                ("pipeline_model_parallel_size",
                 "a stage would have to slice every kind's stack "
                 "(parallel/pipeline.py slices one)"),
                ("tensor_model_parallel_size",
                 "the convolution's channels and the 64-dim heads are not laid out "
                 "over the model axis"),
                ("context_parallel_size", halo)):
            if int(ds.get(key, 1) or 1) > 1:
                raise ValueError(f"distributed_strategy.{key} > 1 is not wired for "
                                 f"model.architecture: lfm2: {why}")
        if bool(ds.get("sequence_parallel", False)):
            raise ValueError("distributed_strategy.sequence_parallel is not wired for "
                             f"model.architecture: lfm2: {halo}")
        experts = int(m.get("num_experts", 0) or 0)
        held = m.get("num_experts_held")
        if held is not None and int(ds.get("expert_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "model.num_experts_held with distributed_strategy."
                "expert_model_parallel_size > 1: a held range is one chip's share "
                "of the experts, with no peer to exchange with (ops/moe.py)")
        if held is not None and not 0 <= int(held[0]) < int(held[1]) <= experts:
            raise ValueError(f"model.num_experts_held {held}: want 0 <= lo < hi <= "
                             f"num_experts {experts}")
        if experts and not bool(m.get("use_expert_bias", True)):
            raise ValueError("model.use_expert_bias: false is not wired for "
                             "model.architecture: lfm2 (selection is by score + bias)")
        rate = float(m.get("router_bias_update_rate") or 0.0)
        if experts and rate <= 0.0:
            raise ValueError(
                f"model.router_bias_update_rate {m.get('router_bias_update_rate')!r}: "
                "the selection bias moves by this step after every optimizer step "
                "(the source keeps it as a buffer and publishes no rule; the rule is "
                "DeepSeek-V3's, its gamma 0.001); a bias that never moves is a router "
                "without the mechanism")
        moe = dataclasses.replace(moe_ops.MoEConfig.from_config({
            "num_experts": experts or 1, "top_k": int(m.get("num_experts_per_tok", 1)),
            "dropless": True, "router_aux_loss_coef": 0.0,
            "normalize_top_k_affinities": bool(m.get("norm_topk_prob", True)),
            "routed_scaling_factor": float(m.get("routed_scaling_factor", 1.0)),
            "experts_held": held, "scoring_func": "sigmoid",
            "router_bias_update_rate": rate,
        }), renorm_eps=RENORM_EPS)
        return cls(
            llama=base, moe=moe, layer_types=layer_types,
            num_dense_layers=int(m.get("num_dense_layers", 2)), conv_L_cache=taps,
            moe_intermediate_size=int(m.get("moe_intermediate_size", 1536)))


def _sparse_stacks(cfg: Lfm2Config) -> list[str]:
    return [kind_name(*kind) for kind in _kind_layers(cfg) if kind[1] == "sparse"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: Lfm2Config, kind: tuple[str, str], dtype):
    """One layer of ``kind`` (unstacked)."""
    lc = cfg.llama
    operator, ffn = kind
    ks = jax.random.split(key, 8)
    h, d, nh, nkv, std = (lc.hidden_size, lc.head_size, lc.num_attention_heads, lc.kv_heads,
                          lc.initializer_range)

    def linear(k, n_in, n_out):
        return linear_ops.init_linear(k, n_in, n_out, shard="replicated", dtype=dtype,
                                      stddev=std)[0]

    params: dict[str, Any] = {
        "operator_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "ffn_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0]}
    if operator == "conv":
        # the taps drawn as the linears, ``[taps, channels]``
        params["conv"] = {"in_proj": linear(ks[0], h, 3 * h),
                          "taps": linear(ks[1], cfg.conv_L_cache, h),
                          "out_proj": linear(ks[3], h, h)}
    else:
        params["attn"] = {"qkv": linear(ks[0], h, (nh + 2 * nkv) * d),
                          "q_norm": norm_ops.init_rms_norm(d, dtype=dtype)[0],
                          "k_norm": norm_ops.init_rms_norm(d, dtype=dtype)[0],
                          "o": linear(ks[3], nh * d, h)}
    if ffn == "dense":
        params["mlp"] = {"gate_up": linear(ks[4], h, 2 * lc.intermediate_size),
                         "down": linear(ks[5], lc.intermediate_size, h)}
    else:
        params["mlp"] = moe_ops.init_moe_params(
            ks[7], h, cfg.moe_intermediate_size, cfg.moe, dtype=dtype, stddev=std)
    return params


def init_params(key: jax.Array, cfg: Lfm2Config, policy: DtypePolicy | None = None):
    """The parameter pytree: ``embed``, ``layers`` one stack per kind
    (``layers/conv_dense``, ``layers/full_sparse``, ...), each in layer order,
    layer ``i`` drawn from the ``i``-th of the layers' keys, and
    ``embedding_norm`` (the source's name for the norm before the tied head)."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    kemb, klayers, khead = jax.random.split(key, 3)
    layer_keys = jax.random.split(klayers, lc.num_layers)
    params: dict[str, Any] = {
        "embed": linear_ops.init_embedding(
            kemb, lc.vocab_size, lc.hidden_size, dtype=dtype, stddev=lc.initializer_range)[0],
        "layers": {
            kind_name(*kind): jax.vmap(lambda k, kind=kind: _init_layer(k, cfg, kind, dtype))(
                layer_keys[jnp.asarray(indices)])
            for kind, indices in _kind_layers(cfg).items()},
        "embedding_norm": norm_ops.init_rms_norm(lc.hidden_size, dtype=dtype)[0],
    }
    if not lc.tie_word_embeddings:
        params["lm_head"], _ = linear_ops.init_linear(
            khead, lc.hidden_size, lc.vocab_size, shard="column", dtype=dtype,
            stddev=lc.initializer_range)
    return params


def param_specs(cfg: Lfm2Config, *, pipeline: bool = False):
    """PartitionSpec tree of ``init_params``: the vocabulary over ``model`` as
    llama's; the layers replicated but for the expert dim over ``expert``
    where the experts are all held (tp is refused at the config)."""
    if pipeline:
        raise NotImplementedError(FAMILY.pipeline.sentence)
    w2 = {"w": P(None, None, None)}   # every leaf leads with its stack's layers
    scale = {"scale": P(None, None)}
    w3 = P(None, None if cfg.moe.experts_held is not None else "expert", None, None)

    def layer(kind):
        operator, ffn = kind
        specs: dict[str, Any] = {"operator_norm": scale, "ffn_norm": scale}
        if operator == "conv":
            specs["conv"] = {"in_proj": w2, "taps": w2, "out_proj": w2}
        else:
            specs["attn"] = {"qkv": w2, "q_norm": scale, "k_norm": scale, "o": w2}
        if ffn == "dense":
            specs["mlp"] = {"gate_up": w2, "down": w2}
        else:
            specs["mlp"] = {"router": {**w2, "bias": P(None, None)},
                            "experts": {"gate_up": w3, "down": w3}}
        return specs

    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": {kind_name(*kind): layer(kind) for kind in _kind_layers(cfg)},
        "embedding_norm": {"scale": P(None)},
    }
    if not cfg.llama.tie_word_embeddings:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _conv_block(lp, x, cfg: Lfm2Config, attention_mask=None, segment_ids=None):
    """``x`` (already normed) through ``in_proj``, the gated convolution and
    ``out_proj``."""
    bcz = linear_ops.apply_linear(lp["in_proj"], x)
    y = conv_ops.gated_short_conv(bcz, lp["taps"]["w"], attention_mask=attention_mask,
                                  segment_ids=segment_ids)
    return linear_ops.apply_linear(lp["out_proj"], y)


def _decoder_layer(lp, x, cos, sin, cfg: Lfm2Config, policy: DtypePolicy,
                   kind: tuple[str, str], attention_mask=None, segment_ids=None):
    """One layer of ``kind`` -> ``(x, stats)``; ``stats`` the routed block's
    per-step values (``ops.moe.moe_block``) and its experts' loads under
    ``COUNTS``, none in a dense layer."""
    lc = cfg.llama
    operator, ffn = kind
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES (attention: the token-mixing
    # half of a layer, whichever operator fills it) and FAMILY_SCOPES
    with jax.named_scope("attention"):
        if operator == "conv":
            with jax.named_scope("short_conv"):
                hidden = norm_ops.apply_rms_norm(lp["operator_norm"], x, eps=lc.rms_norm_eps)
                hidden = _conv_block(lp["conv"], hidden, cfg, attention_mask=attention_mask,
                                     segment_ids=segment_ids)
                x = shd.constrain(x + hidden, aspec)
        else:
            hidden = norm_ops.apply_rms_norm(lp["operator_norm"], x, eps=lc.rms_norm_eps)
            hidden = llama._attention_block(
                lp["attn"], hidden, cos, sin, lc, policy, attention_mask=attention_mask,
                segment_ids=segment_ids, sliding_window=None)
            x = shd.constrain(x + hidden, aspec)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            hidden = norm_ops.apply_rms_norm(lp["ffn_norm"], x, eps=lc.rms_norm_eps)
            x = shd.constrain(x + llama._mlp_block(lp["mlp"], hidden), aspec)
        return x, {}
    # moe_block opens the "moe" scope itself; the norm before it and the
    # residual after it belong with it (as models/mixtral.py)
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["ffn_norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec)
    with jax.named_scope("moe"):
        x = shd.constrain(x + hidden, aspec)
        stats = {**aux["stats"], COUNTS: aux["expert_counts"],
                 "moe/bias_abs_max": jnp.max(jnp.abs(lp["mlp"]["router"]["bias"]))}
    return x, stats


def decoder_stack(layers, x, cos, sin, cfg: Lfm2Config, policy: DtypePolicy, *,
                  attention_mask=None, segment_ids=None):
    """The whole stack by ``stack_plan`` -> ``(x, {sparse kind: its layers'
    stats})``.  A run of one layer stays merged with its rerun (as
    models/laguna.py)."""
    conv_only = dataclasses.replace(cfg.llama, attention_impl="core")

    def run_of(kind):
        def body(x, lp):
            return _decoder_layer(_cast_layer(lp, policy), x, cos, sin, cfg, policy, kind,
                                  attention_mask=attention_mask, segment_ids=segment_ids)
        # a convolution layer holds no flash kernel: its stack's ``remat``
        # entry says nothing of one
        body = llama.checkpoint_layer(
            body, cfg.llama if kind[0] == "full_attention" else conv_only,
            stack=kind_name(*kind))
        return lambda x, stack: jax.lax.scan(body, x, stack)

    x, all_stats = run_stacks(layers, x, cfg.kinds, run_of)
    return x, {kind: stats for kind, stats in stats_by_kind(cfg.kinds, all_stats).items()
               if stats}


def forward(params, batch: dict[str, jax.Array], cfg: Lfm2Config, policy: DtypePolicy, *,
            shift_labels: bool = True, return_logits: bool = False):
    """Causal-LM forward -> ``(loss, aux)``: llama's loss and nothing beside
    it (no auxiliary loss); ``aux`` carries the experts' loads, one entry a
    sparse kind."""
    lc = cfg.llama
    input_ids = batch["input_ids"]
    attention_mask, segment_ids = batch.get("attention_mask"), batch.get("segment_ids")
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, shd.act_spec(lc.sequence_parallel, lc.context_parallel))
    inv_freq = rope_ops.rope_frequencies(lc.head_size, theta=lc.rope_theta)
    cos, sin = rope_ops.rope_cos_sin(
        llama.positions_for(input_ids, attention_mask, segment_ids), inv_freq,
        dtype=jnp.float32)
    x, by_kind = decoder_stack(params["layers"], x, cos, sin, cfg, policy,
                               attention_mask=attention_mask, segment_ids=segment_ids)
    aux: dict[str, Any] = {}
    for kind, stats in by_kind.items():
        aux[f"{COUNTS}/{kind_name(*kind)}"] = stats.pop(COUNTS)
    # the expert blocks' scalars (moe/...), the largest over the layers
    for name in sorted({name for stats in by_kind.values() for name in stats}):
        aux[name] = jnp.max(jnp.stack(
            [jnp.max(stats[name]) for stats in by_kind.values() if name in stats]))
    with jax.named_scope("ce_head"):
        hidden = norm_ops.apply_rms_norm(params["embedding_norm"], x, eps=lc.rms_norm_eps)
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


def flops_breakdown(cfg: Lfm2Config, seq_len: int) -> dict[str, float]:
    """``Family.flops_breakdown``: under ``attention`` the token-mixing half
    of every layer (a convolution layer's two projections, its taps and its
    two gates; an attention layer's projections and causal scores); of the
    routed experts only the slots this program holds (the expected ``top_k *
    held / E`` a token)."""
    lc = cfg.llama
    h, d, nh, nkv = lc.hidden_size, lc.head_size, lc.num_attention_heads, lc.kv_heads
    conv = 2 * h * 4 * h + 2 * cfg.conv_L_cache * h + 2 * h
    attention = (2 * h * (nh + 2 * nkv) * d + 2 * nh * d * h
                 + 4 * nh * d * (seq_len + 1) / 2)
    n_conv = cfg.layer_types.count("conv")
    n_sparse = cfg.ffn_types.count("sparse")
    slots = cfg.moe.top_k * cfg.moe.experts_resident / cfg.moe.num_experts
    return {
        "attention": float(n_conv * conv + (lc.num_layers - n_conv) * attention),
        "mlp": float((lc.num_layers - n_sparse) * 6 * h * lc.intermediate_size
                     + n_sparse * 6 * h * cfg.moe_intermediate_size * slots),
        "router": float(n_sparse * 2 * h * cfg.moe.num_experts),
        "head": 2.0 * h * lc.vocab_size,
    }


def _run_facts(cfg: Lfm2Config, sched) -> dict:
    kinds: dict[str, int] = {}
    for kind in cfg.kinds:
        kinds[kind_name(*kind)] = kinds.get(kind_name(*kind), 0) + 1
    facts: dict[str, Any] = {
        "layer_kinds": kinds,
        "operator_kinds": {t: cfg.layer_types.count(t) for t in OPERATOR_TYPES},
        "short_conv": {"taps": cfg.conv_L_cache, "way": conv_ops.WAY,
                       "bytes_per_token": conv_ops.bytes_per_token(cfg.hidden_size)}}
    if cfg.sparse:
        facts["moe_score_func"] = cfg.moe.score_func
        if cfg.moe.experts_held is not None:
            facts["moe_experts_held"] = [*cfg.moe.experts_held, cfg.moe.num_experts]
    return facts


def _after_update(cfg: Lfm2Config) -> Optional[AfterUpdate]:
    """The selection bias's rule: every sparse layer's ``router/bias`` moves
    by the loads its experts met in the step's tokens."""
    stacks = _sparse_stacks(cfg)
    if not stacks:
        return None

    def apply(params, aux):
        layers = dict(params["layers"])
        for name in stacks:
            stack = layers[name]
            router = stack["mlp"]["router"]
            moved = moe_ops.bias_update(router["bias"], aux[f"{COUNTS}/{name}"],
                                        cfg.moe.bias_update_rate)
            layers[name] = {**stack, "mlp": {**stack["mlp"],
                                             "router": {**router, "bias": moved}}}
        return {**params, "layers": layers}

    return AfterUpdate(reads=tuple(f"{COUNTS}/{name}" for name in stacks), apply=apply)


def _logits(cfg: Lfm2Config, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, _ = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, 0.0

    return fwd


FAMILY = Family(
    name="lfm2",
    config_from=Lfm2Config.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    # llama's layout with the attention layers' heads and the dense layers'
    # width: the planner prices neither the convolution nor the experts
    plan_shape=lambda cfg: llama.plan_shape(cfg.llama),
    logits=_logits,
    head=Refused(
        "model.architecture: lfm2 has no head for the preference losses: the norm "
        "before its tied head is the leaf embedding_norm, which llama.head does not "
        "read"),
    pipeline=Refused(
        "pipeline parallelism not wired for Lfm2Config: a stage would have "
        "to slice every kind's stack (parallel/pipeline.py slices one)"),
    onef1b_head=Refused(
        "Lfm2Config: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=Refused(
        "model.architecture: lfm2 has no cached decode: two kinds of state, an "
        "attention layer's keys and values and a convolution layer's last "
        "conv_L_cache - 1 gate products (models/decode.py holds one kind of cache)"),
    run_facts=_run_facts,
    after_update=_after_update,
)
