"""Kanana-2 / DeepSeek-V3-family decoder (``model_type: deepseek_v3``): latent
attention and sigmoid-routed experts with a selection bias.

Every layer is a pre-norm attention block and a pre-norm MLP, as in
``models.llama``; what fills the two slots, by the source's keys:

- **multi-head latent attention** (MLA).  ``q = x Wq`` per head
  ``qk_nope_head_dim + qk_rope_head_dim`` wide (no query latent:
  ``q_lora_rank`` null).  ``x Wkva`` gives a latent of ``kv_lora_rank`` and ONE
  key of ``qk_rope_head_dim`` a token, shared by every head; the latent is
  RMS-normed and ``Wkvb`` takes it up to per-head keys of ``qk_nope_head_dim``
  and values of ``v_head_dim``.  Rope (``rope_theta``, pairs ``(2i, 2i + 1)``:
  ``rope_interleave``) turns the ``qk_rope_head_dim`` part of q and the
  shared key.  A head scores over ``d_qk = nope + rope`` dims, scaled by
  ``1 / sqrt(d_qk)``, and weighs values of ``v_head_dim``: the flash kernels
  take ``d_qk != d_v`` (``ops/flash_attention.py``), fed a k materialised
  ``[b, s, heads, d_qk]`` with the shared key broadcast over the heads.
- the first ``first_k_dense_replace`` layers a SwiGLU of ``intermediate_size``;
  the others ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token: scores ``sigmoid(h Wr)`` in float32, chosen
  by ``score + bias``, weighed by the scores alone over their sum times
  ``routed_scaling_factor`` (``ops.moe.route``), beside ONE ungated SwiGLU of
  ``n_shared_experts x moe_intermediate_size``.  No auxiliary loss
  (``topk_method: noaux_tc``): the bias (``.../router/bias``) takes no
  gradient and no decay and after each optimizer step moves by
  ``router_bias_update_rate x sign(mean load - load)`` (``ops.moe.bias_update``,
  the family's ``after_update``; the loads are the loss's aux
  ``moe_expert_counts [sparse layers, experts]``).

The parameters hold one stack per kind (``layers/dense``, ``layers/sparse``),
each one ``lax.scan`` of checkpointed layers.  ``num_experts_held: [lo, hi]``
makes the program one chip of an expert-parallel deployment, alone
(``ops.moe._held_experts``).

Not wired (each refused by name): pipeline stages, cached decode (the cache
of this attention is the latent, not keys and values), tensor parallelism
(the latent and the shared key are not laid out over the model axis), context
parallelism, a query latent (``q_lora_rank``), ``rope_scaling`` (YaRN's
``mscale`` on the softmax scale), group-limited selection with more than one
group, a held range together with expert parallelism; ``tools/convert.py``
does not know the family's leaves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import AfterUpdate, Family, Refused
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

KINDS = ("dense", "sparse")
#: the loss's aux entry the bias's rule reads: ``[sparse layers, experts]``
COUNTS = "moe_expert_counts"


@dataclasses.dataclass(frozen=True)
class KananaConfig:
    """Llama knobs (``llama``: the widths every layer shares, the dense
    MLP's ``intermediate_size``, fusions, recomputation) + the latent
    attention's dims + the routed block (``moe``)."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_interleave: bool = True
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 768
    n_shared_experts: int = 0

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
        return self.llama.num_attention_heads

    @property
    def head_dim(self):
        return self.v_head_dim

    @property
    def family(self) -> Family:
        return FAMILY

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layers_of(self) -> dict[str, int]:
        """Kind -> how many layers: the dense ones lead."""
        dense = min(self.first_k_dense_replace, self.num_layers) if self.sparse else self.num_layers
        return {"dense": dense, "sparse": self.num_layers - dense}

    @property
    def sparse(self) -> bool:
        return self.moe.num_experts > 1

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the family is not wired for, each
        # by its key's name
        m, ds = dict(model_cfg or {}), dict(ds_cfg or {})
        base = llama.LlamaConfig.from_config(m, ds)
        if m.get("q_lora_rank") is not None:
            raise ValueError("model.q_lora_rank: a query latent is not wired for "
                             "model.architecture: kanana (q = x Wq)")
        if m.get("rope_scaling") is not None:
            raise ValueError("model.rope_scaling is not wired for model.architecture: "
                             "kanana (YaRN's mscale would scale the softmax)")
        for key in ("n_group", "topk_group"):
            if int(m.get(key, 1) or 1) > 1:
                raise ValueError(f"model.{key} > 1: group-limited selection is not wired "
                                 "for model.architecture: kanana")
        if base.fuse_qkv is False:
            raise ValueError("model.fuse_qkv is not a key of model.architecture: kanana")
        for key, why in (
                ("pipeline_model_parallel_size",
                 "a stage would have to slice both kinds' stacks "
                 "(parallel/pipeline.py slices one)"),
                ("tensor_model_parallel_size",
                 "the latent and the key all heads share are not laid out over "
                 "the model axis"),
                ("context_parallel_size",
                 "the ring's chunks would carry the latent, not keys and values")):
            if int(ds.get(key, 1) or 1) > 1:
                raise ValueError(f"distributed_strategy.{key} > 1 is not wired for "
                                 f"model.architecture: kanana: {why}")
        experts = int(m.get("n_routed_experts", 0) or 0)
        held = m.get("num_experts_held")
        if held is not None and int(ds.get("expert_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "model.num_experts_held with distributed_strategy."
                "expert_model_parallel_size > 1: a held range is one chip's share "
                "of the experts, with no peer to exchange with (ops/moe.py)")
        if held is not None and not 0 <= int(held[0]) < int(held[1]) <= experts:
            raise ValueError(f"model.num_experts_held {held}: want 0 <= lo < hi <= "
                             f"n_routed_experts {experts}")
        method = str(m.get("topk_method", "noaux_tc"))
        if method != "noaux_tc":
            raise ValueError(f"model.topk_method {method!r}: wired is noaux_tc (a "
                             "selection bias, no auxiliary loss)")
        rate = float(m.get("router_bias_update_rate") or 0.0)
        if experts and rate <= 0.0:
            raise ValueError(
                f"model.router_bias_update_rate {m.get('router_bias_update_rate')!r}: "
                "under topk_method noaux_tc the selection bias moves by this step "
                "after every optimizer step (DeepSeek-V3's 0.001); a bias that "
                "never moves is a router without the mechanism")
        moe = moe_ops.MoEConfig.from_config({
            "num_experts": experts or 1, "top_k": int(m.get("num_experts_per_tok", 1)),
            "dropless": True, "router_aux_loss_coef": 0.0,
            "normalize_top_k_affinities": bool(m.get("norm_topk_prob", True)),
            "routed_scaling_factor": float(m.get("routed_scaling_factor", 1.0)),
            "experts_held": held,
            "scoring_func": str(m.get("scoring_func", "sigmoid")),
            "router_bias_update_rate": rate,
        })
        if moe.score_func != "sigmoid":
            raise ValueError(f"model.scoring_func {moe.score_func!r}: wired for "
                             "model.architecture: kanana is sigmoid")
        return cls(
            llama=base, moe=moe,
            qk_nope_head_dim=int(m.get("qk_nope_head_dim", 128)),
            qk_rope_head_dim=int(m.get("qk_rope_head_dim", 64)),
            v_head_dim=int(m.get("v_head_dim", 128)),
            kv_lora_rank=int(m.get("kv_lora_rank", 512)),
            rope_interleave=bool(m.get("rope_interleave", True)),
            first_k_dense_replace=int(m.get("first_k_dense_replace", 1)),
            moe_intermediate_size=int(m.get("moe_intermediate_size", 768)),
            n_shared_experts=int(m.get("n_shared_experts", 0) or 0))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: KananaConfig, kind: str, dtype):
    """One layer of ``kind`` (unstacked)."""
    lc = cfg.llama
    ks = jax.random.split(key, 8)
    h, nh, std = lc.hidden_size, lc.num_attention_heads, lc.initializer_range

    def linear(k, n_in, n_out):
        return linear_ops.init_linear(k, n_in, n_out, shard="replicated", dtype=dtype,
                                      stddev=std)[0]

    def swiglu(width):
        return {"gate_up": linear(ks[4], h, 2 * width), "down": linear(ks[5], width, h)}

    params: dict[str, Any] = {
        "input_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "post_attn_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "attn": {
            "q": linear(ks[0], h, nh * cfg.qk_head_dim),
            "kv_a": linear(ks[1], h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": norm_ops.init_rms_norm(cfg.kv_lora_rank, dtype=dtype)[0],
            "kv_b": linear(ks[2], cfg.kv_lora_rank,
                           nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "o": linear(ks[3], nh * cfg.v_head_dim, h)},
    }
    if kind == "dense":
        params["mlp"] = swiglu(lc.intermediate_size)
    else:
        params["mlp"] = moe_ops.init_moe_params(
            ks[7], h, cfg.moe_intermediate_size, cfg.moe, dtype=dtype, stddev=std)
        if cfg.n_shared_experts:
            params["mlp"]["shared"] = swiglu(cfg.n_shared_experts * cfg.moe_intermediate_size)
    return params


def init_params(key: jax.Array, cfg: KananaConfig, policy: DtypePolicy | None = None):
    """The parameter pytree: llama's top level, ``layers`` one stack per kind
    that has layers (``layers/dense``, ``layers/sparse``), layer ``i`` drawn
    from the ``i``-th of the layers' keys."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    kemb, klayers, khead = jax.random.split(key, 3)
    layer_keys = jax.random.split(klayers, lc.num_layers)
    layers, at = {}, 0
    for kind, n in cfg.layers_of.items():
        if n:
            layers[kind] = jax.vmap(lambda k, kind=kind: _init_layer(k, cfg, kind, dtype))(
                layer_keys[at:at + n])
        at += n
    params: dict[str, Any] = {
        "embed": linear_ops.init_embedding(
            kemb, lc.vocab_size, lc.hidden_size, dtype=dtype, stddev=lc.initializer_range)[0],
        "layers": layers,
        "final_norm": norm_ops.init_rms_norm(lc.hidden_size, dtype=dtype)[0],
    }
    if not lc.tie_word_embeddings:
        params["lm_head"], _ = linear_ops.init_linear(
            khead, lc.hidden_size, lc.vocab_size, shard="column", dtype=dtype,
            stddev=lc.initializer_range)
    return params


def param_specs(cfg: KananaConfig, *, pipeline: bool = False):
    """PartitionSpec tree of ``init_params``: the vocabulary over ``model`` as
    llama's; the layers replicated but for the expert dim over ``expert``
    where the experts are all held (tp is refused at the config)."""
    if pipeline:
        raise NotImplementedError(FAMILY.pipeline.sentence)
    w2 = {"w": P(None, None, None)}   # every leaf leads with its stack's layers
    scale = {"scale": P(None, None)}
    w3 = P(None, None if cfg.moe.experts_held is not None else "expert", None, None)
    swiglu = {"gate_up": w2, "down": w2}

    def layer(kind):
        specs: dict[str, Any] = {
            "input_norm": scale, "post_attn_norm": scale,
            "attn": {"q": w2, "kv_a": w2, "kv_norm": scale, "kv_b": w2, "o": w2}}
        if kind == "dense":
            specs["mlp"] = swiglu
        else:
            specs["mlp"] = {"router": {**w2, "bias": P(None, None)},
                            "experts": {"gate_up": w3, "down": w3}}
            if cfg.n_shared_experts:
                specs["mlp"]["shared"] = swiglu
        return specs

    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": {kind: layer(kind) for kind, n in cfg.layers_of.items() if n},
        "final_norm": {"scale": P(None)},
    }
    if not cfg.llama.tie_word_embeddings:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mla_block(lp, x, cos, sin, cfg: KananaConfig, policy: DtypePolicy,
               attention_mask=None, segment_ids=None):
    """``x`` (already normed) through the latent attention and ``o``."""
    lc = cfg.llama
    b, s, _ = x.shape
    nh, dn, dr, dv = (lc.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    rope = rope_ops.apply_rope_interleaved if cfg.rope_interleave else rope_ops.apply_rope
    q = linear_ops.apply_linear(lp["q"], x).reshape(b, s, nh, dn + dr)
    q = shd.constrain(q, shd.heads_spec(lc.context_parallel))
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cos, sin)], axis=-1)
    # what MLA adds beside q, the kernels and o (telemetry.spans.FAMILY_SCOPES)
    with jax.named_scope("mla_latent"):
        latent, k_pe = jnp.split(
            linear_ops.apply_linear(lp["kv_a"], x), [cfg.kv_lora_rank], axis=-1)
        latent = norm_ops.apply_rms_norm(lp["kv_norm"], latent, eps=lc.rms_norm_eps)
        kv = linear_ops.apply_linear(lp["kv_b"], latent).reshape(b, s, nh, dn + dv)
        k_pe = rope(k_pe[:, :, None, :], cos, sin)   # one rotated key a token
        # fed whole: k as wide as q, the shared key repeated per head
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, nh, dr))], axis=-1)
        v = kv[..., dn:]
    out = attn_ops.attention(
        q, k, v, impl=lc.attention_impl, causal=True, sliding_window=None,
        softmax_dtype=policy.softmax_dtype, attention_mask=attention_mask,
        segment_ids=segment_ids, block_q=lc.flash_block_q, block_kv=lc.flash_block_kv,
        keep_flash_outputs=llama._keeps_flash_outputs(lc))
    return linear_ops.apply_linear(lp["o"], out.reshape(b, s, nh * dv))


def _cast_layer(lp, policy: DtypePolicy):
    """The per-layer cast to the compute dtype of all but the router and the
    expert weights (as models/laguna.py)."""
    cast = policy.cast_to_compute(lp)
    if "experts" not in lp["mlp"]:
        return cast
    return {**cast, "mlp": {**cast["mlp"], "experts": lp["mlp"]["experts"],
                            "router": lp["mlp"]["router"]}}


def _decoder_layer(lp, x, cos, sin, cfg: KananaConfig, policy: DtypePolicy, kind: str,
                   attention_mask=None, segment_ids=None):
    """One layer of ``kind`` -> ``(x, stats)``; ``stats`` the routed block's
    per-step values (``ops.moe.moe_block``) and its experts' loads under
    ``COUNTS``, none in a dense layer."""
    lc = cfg.llama
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("attention"):
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=lc.rms_norm_eps)
        hidden = _mla_block(lp["attn"], hidden, cos, sin, cfg, policy,
                            attention_mask=attention_mask, segment_ids=segment_ids)
        x = shd.constrain(x + hidden, aspec)
    if kind == "dense":
        with jax.named_scope("mlp"):
            hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
            x = shd.constrain(x + llama._mlp_block(lp["mlp"], hidden), aspec)
        return x, {}
    # moe_block opens the "moe" scope itself; the norm before it and the
    # residual after it belong with it (as models/mixtral.py)
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec)
    with jax.named_scope("moe"):
        x = shd.constrain(x + hidden, aspec)
        stats = {**aux["stats"], COUNTS: aux["expert_counts"],
                 "moe/bias_abs_max": jnp.max(jnp.abs(lp["mlp"]["router"]["bias"]))}
    return x, stats


def decoder_stack(layers, x, cos, sin, cfg: KananaConfig, policy: DtypePolicy, *,
                  attention_mask=None, segment_ids=None):
    """The dense layers, then the sparse ones, each kind one scan of
    checkpointed layers -> ``(x, stats of the sparse scan, stacked by layer)``.

    A run of ONE layer (the family's dense layer 0) is checkpointed with
    ``prevent_cse``: a scan of length 1 is unrolled, and without the barrier
    the compiler merges the layer's rerun with its first run, so that nothing
    of the layer is rematerialized and every activation of it (q and k of
    ``[b, heads, s, 192]`` among them: 1.17 GiB at the benchmark's cut) lives
    through the whole step.  With them held, the step that keeps the sparse
    layers' kernel outputs was refused for one v5e by 148 MiB while the held
    experts' operand was 4 x the even share of the rows; since 3 x it fits,
    with 0.36 GiB more temporaries than the released step's (0.46 at 3 x;
    tests/test_tpu_compile.py).  Released, the price is that one layer's
    projections and MLP run forward twice."""
    stats: dict = {}
    for kind in KINDS:
        if kind not in layers:
            continue

        def body(x, lp, kind=kind):
            return _decoder_layer(_cast_layer(lp, policy), x, cos, sin, cfg, policy, kind,
                                  attention_mask=attention_mask, segment_ids=segment_ids)
        body = llama.checkpoint_layer(body, cfg.llama, stack=kind,
                                      prevent_cse=cfg.layers_of[kind] == 1)
        x, stats = jax.lax.scan(body, x, layers[kind])
    return x, stats


def forward(params, batch: dict[str, jax.Array], cfg: KananaConfig, policy: DtypePolicy, *,
            shift_labels: bool = True, return_logits: bool = False):
    """Causal-LM forward -> ``(loss, aux)``: llama's loss and nothing beside
    it (no auxiliary loss); ``aux`` carries the experts' loads."""
    lc = cfg.llama
    input_ids = batch["input_ids"]
    attention_mask, segment_ids = batch.get("attention_mask"), batch.get("segment_ids")
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, shd.act_spec(lc.sequence_parallel, lc.context_parallel))
    inv_freq = rope_ops.rope_frequencies(cfg.qk_rope_head_dim, theta=lc.rope_theta)
    cos, sin = rope_ops.rope_cos_sin(
        llama.positions_for(input_ids, attention_mask, segment_ids), inv_freq,
        dtype=jnp.float32)
    x, stats = decoder_stack(params["layers"], x, cos, sin, cfg, policy,
                             attention_mask=attention_mask, segment_ids=segment_ids)
    aux: dict[str, Any] = {}
    if stats:
        aux[COUNTS] = stats.pop(COUNTS)
        # the expert blocks' scalars (moe/...), the largest over the layers
        aux.update({name: jnp.max(v) for name, v in stats.items()})
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


def flops_breakdown(cfg: KananaConfig, seq_len: int) -> dict[str, float]:
    """``Family.flops_breakdown``: the latent attention's five projections and
    its causal scores over ``d_qk`` and ``d_v``; of the routed experts only
    the slots this program holds (the expected ``top_k * held / E`` a token)."""
    lc = cfg.llama
    h, nh, n = lc.hidden_size, lc.num_attention_heads, lc.num_layers
    projections = (h * nh * cfg.qk_head_dim + h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                   + cfg.kv_lora_rank * nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                   + nh * cfg.v_head_dim * h)
    scores = nh * (cfg.qk_head_dim + cfg.v_head_dim) * (seq_len + 1) / 2
    n_sparse = cfg.layers_of["sparse"]
    slots = cfg.moe.top_k * cfg.moe.experts_resident / cfg.moe.num_experts
    sparse = 6 * h * cfg.moe_intermediate_size * (cfg.n_shared_experts + slots)
    return {
        "attention": float(n * 2 * (projections + scores)),
        "mlp": float((n - n_sparse) * 6 * h * lc.intermediate_size + n_sparse * sparse),
        "router": float(n_sparse * 2 * h * cfg.moe.num_experts),
        "head": 2.0 * h * lc.vocab_size,
    }


def _run_facts(cfg: KananaConfig, sched) -> dict:
    facts: dict[str, Any] = {
        "attention_kind": "mla",
        "mla_dims": [cfg.qk_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
                     cfg.qk_rope_head_dim],
        "layer_kinds": {"mlp": dict(cfg.layers_of)}}
    if cfg.sparse:
        facts["moe_score_func"] = cfg.moe.score_func
        if cfg.moe.experts_held is not None:
            facts["moe_experts_held"] = [*cfg.moe.experts_held, cfg.moe.num_experts]
    return facts


def _after_update(cfg: KananaConfig) -> Optional[AfterUpdate]:
    """The selection bias's rule: every sparse layer's ``router/bias`` moves
    by the loads its experts met in the step's tokens."""
    if not cfg.layers_of["sparse"]:
        return None

    def apply(params, aux):
        sparse = params["layers"]["sparse"]
        router = sparse["mlp"]["router"]
        moved = moe_ops.bias_update(router["bias"], aux[COUNTS], cfg.moe.bias_update_rate)
        return {**params, "layers": {**params["layers"], "sparse": {
            **sparse, "mlp": {**sparse["mlp"], "router": {**router, "bias": moved}}}}}

    return AfterUpdate(reads=(COUNTS,), apply=apply)


def _logits(cfg: KananaConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, _ = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, 0.0

    return fwd


def _plan_shape(cfg: KananaConfig) -> dict[str, Any]:
    # llama's layout with the value dims for a head and the dense layer's
    # width: the planner prices neither the latent nor the experts
    return {**llama.plan_shape(cfg.llama), "num_kv_heads": cfg.num_attention_heads,
            "head_dim": cfg.v_head_dim}


FAMILY = Family(
    name="kanana",
    config_from=KananaConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    plan_shape=_plan_shape,
    logits=_logits,
    head=lambda cfg, policy, **kw: llama.head(cfg.llama, policy, **kw),
    pipeline=Refused(
        "pipeline parallelism not wired for KananaConfig: a stage would have "
        "to slice both kinds' stacks (parallel/pipeline.py slices one)"),
    onef1b_head=Refused(
        "KananaConfig: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=Refused(
        "model.architecture: kanana has no cached decode: the cache of latent "
        "attention is the normed latent and the shared rotated key, and "
        "models/decode.py caches per-head keys and values"),
    run_facts=_run_facts,
    after_update=_after_update,
)
