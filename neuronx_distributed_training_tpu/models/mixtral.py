"""Mixtral-family decoder (MoE), TPU-native.

Functional re-design of the reference's ``models/hf_models/modeling_mixtral.py``
(893 LoC): Llama-style attention blocks (sliding-window causal) with the MLP
replaced by a routed mixture of SwiGLU experts, the router-logit threading that
feeds the load-balancing aux loss (reference ``modeling_mixtral.py:440-549``
threads ``past_router_logits`` through layers; here the scan carry accumulates
the per-layer aux loss directly, which is PP-friendly for the same reason), and
``router_aux_loss_coef`` scaling at the loss (``modeling_mixtral.py:872-878``).

Shares the attention/norm/rope machinery with ``models.llama`` — the decoder
differs only in the MLP slot.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import Family, Refused
from neuronx_distributed_training_tpu.ops import cross_entropy as ce_ops
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    """Mixtral architecture = Llama knobs + MoE block + sliding window
    (reference ``mixtral_model.py:24-96``, ``hf_mixtral_8x7b_config.yaml``)."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    moe_frequency: int = 1  # every Nth layer is MoE; 1 = all (Mixtral)

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
    def family(self) -> Family:
        return FAMILY

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        m = dict(model_cfg or {})
        base = llama.LlamaConfig.from_config(m, ds_cfg)
        # Mixtral defaults that differ from Llama
        if m.get("sliding_window") is None and m.get("use_sliding_window", False):
            base = dataclasses.replace(base, sliding_window=4096)
        return cls(
            llama=base,
            moe=moe_ops.MoEConfig.from_config(m.get("moe", {})),
            moe_frequency=int(m.get("moe", {}).get("frequency", 1) or 1),
        )


def num_moe_layers(cfg: MixtralConfig) -> int:
    """Layer ``i`` is MoE iff ``i % moe_frequency == 0`` (reference
    ``modeling_mixtral.py:444-451``)."""
    f = cfg.moe_frequency
    if cfg.llama.num_layers % f != 0:
        raise ValueError(
            f"num_layers {cfg.llama.num_layers} must divide by moe "
            f"frequency {f}"
        )
    return cfg.llama.num_layers // f


def init_params(key: jax.Array, cfg: MixtralConfig, policy: DtypePolicy | None = None):
    """Llama skeleton with MoE MLPs every ``moe_frequency``-th layer.

    ``moe_frequency == 1`` (Mixtral proper): every layer's MLP is
    router+experts, stacked ``[L, ...]``.  ``> 1``: the stack is grouped as
    ``[L/f]`` groups of (1 MoE layer + f-1 dense layers); attention/norm
    params stay flat ``[L, ...]`` and ``layers.mlp`` becomes
    ``{"moe": [L/f, ...], "dense": [L/f, f-1, ...]}``.
    """
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    params = llama.init_params(key, lc, policy)

    def init_layer_moe(k):
        return moe_ops.init_moe_params(
            k, lc.hidden_size, lc.intermediate_size, cfg.moe,
            dtype=dtype, stddev=lc.initializer_range,
        )

    g = num_moe_layers(cfg)
    moe_keys = jax.random.split(jax.random.fold_in(key, 999), g)
    moe = jax.vmap(init_layer_moe)(moe_keys)
    if cfg.moe_frequency == 1:
        params["layers"]["mlp"] = moe
    else:
        f = cfg.moe_frequency
        dense = jax.tree_util.tree_map(
            lambda x: x.reshape((g, f) + x.shape[1:])[:, 1:],
            params["layers"]["mlp"],
        )
        params["layers"]["mlp"] = {"moe": moe, "dense": dense}
    return params


def param_specs(cfg: MixtralConfig, *, pipeline: bool = False):
    specs = llama.param_specs(cfg.llama, pipeline=pipeline)
    lead = "pipe" if pipeline else None
    moe_specs = jax.tree_util.tree_map(
        lambda s: P(*((lead,) + tuple(s))), moe_ops.moe_param_specs(cfg.moe),
        is_leaf=lambda x: isinstance(x, P),
    )
    if cfg.moe_frequency == 1:
        specs["layers"]["mlp"] = moe_specs
    else:
        # dense leaves gain the inner (f-1) group dim after the layer dim
        dense_specs = jax.tree_util.tree_map(
            lambda s: P(*((tuple(s)[0], None) + tuple(s)[1:])),
            specs["layers"]["mlp"],
            is_leaf=lambda x: isinstance(x, P),
        )
        specs["layers"]["mlp"] = {"moe": moe_specs, "dense": dense_specs}
    return specs


def _group_xs(cfg: MixtralConfig, layer_stack):
    """Grouped scan inputs (see ``ops.moe.group_interleaved_stack``)."""
    return moe_ops.group_interleaved_stack(cfg.moe_frequency, layer_stack)


def _cast_layer(lp, policy: DtypePolicy):
    """The per-layer cast to the compute dtype (see llama) of all but the
    expert weights: ``moe_block`` casts those where it multiplies them and
    hands their gradients back in the dtype they arrive in, so the float32
    sum over rows (and over chips) reaches the master weights without a
    round trip through the compute dtype."""
    cast = policy.cast_to_compute(lp)
    return {**cast, "mlp": {**cast["mlp"], "experts": lp["mlp"]["experts"]}}


def _grouped_scan(cfg: MixtralConfig, layer_stack, cos, sin, policy,
                  attention_mask=None, segment_ids=None):
    """(xs, body) for the dense/MoE interleave scan over [G] groups.

    Shared by ``forward`` and the pipeline ``stage_fn``: each group runs one
    MoE layer then ``f-1`` dense llama layers (see ``_group_xs``).
    """
    lc = cfg.llama
    xs = _group_xs(cfg, layer_stack)

    def body(carry, gp):
        x, aux_acc = carry
        # per-group cast inside the scan (one group's bf16 copy live at a time)
        x, aux, stats = _decoder_layer(
            _cast_layer(gp["moe"], policy), x, cos, sin, cfg, policy,
            attention_mask=attention_mask, segment_ids=segment_ids)

        def dense_body(x2, dlp):
            return llama._decoder_layer(
                policy.cast_to_compute(dlp), x2, cos, sin, lc, policy,
                attention_mask=attention_mask, segment_ids=segment_ids,
            ), None

        x, _ = jax.lax.scan(dense_body, x, gp["dense"])
        return (x, aux_acc + aux), stats

    return xs, body


def _decoder_layer(lp, x, cos, sin, cfg: MixtralConfig, policy: DtypePolicy,
                   attention_mask=None, segment_ids=None, return_kv=False):
    """Pre-LN attention + MoE block; returns (x, aux_loss, stats[, (k, v)]),
    ``stats`` the block's per-step scalars (``ops.moe.moe_block``)."""
    lc = cfg.llama
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("attention"):
        residual = x
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=lc.rms_norm_eps)
        hidden = llama._attention_block(lp["attn"], hidden, cos, sin, lc, policy,
                                        attention_mask=attention_mask,
                                        segment_ids=segment_ids,
                                        return_kv=return_kv)
        kv = None
        if return_kv:
            hidden, kv = hidden
        x = shd.constrain(residual + hidden, aspec)
    residual = x
    # moe_block opens the "moe" scope itself (models/gpt.py calls it too); the
    # norm before it and the router loss and residual after it belong with it
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec,
    )
    with jax.named_scope("moe"):
        aux_loss = moe_ops.weighted_router_loss(
            aux["router_logits"], aux["expert_idx"], cfg.moe)
        x = shd.constrain(residual + hidden, aspec)
    if return_kv:
        return x, aux_loss, aux["stats"], kv
    return x, aux_loss, aux["stats"]


def pipeline_hooks(cfg: MixtralConfig, policy: DtypePolicy, *,
                   shift_labels: bool = True):
    """(embed_fn, stage_fn, loss_fn) for ``parallel.pipeline.pipeline_loss``.

    ``stage_fn`` returns ``(x, aux)`` (use ``stage_aux=True``): the router
    aux-loss accumulates per stage and crosses pipe ranks as a psum'd scalar —
    the TPU-native form of the reference threading ``past_router_logits``
    through pipeline stages (``modeling_mixtral.py:440-549``).  The caller
    scales the psum'd total by ``1 / (num_microbatches * num_moe_layers(cfg))``
    (only router-bearing layers contribute).
    """
    lc = cfg.llama
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)

    def embed_fn(params, mb):
        x = linear_ops.apply_embedding(
            params["embed"], mb["input_ids"], compute_dtype=policy.compute_dtype,
        )
        return shd.constrain(x, aspec)

    def stage_fn(local_layers, x, mb):
        cos, sin = llama._rope_for(mb["input_ids"], lc)
        ll = local_layers

        if cfg.moe_frequency == 1:

            def body(carry, lp):
                x, aux_acc = carry
                lp = _cast_layer(lp, policy)
                x, aux, stats = _decoder_layer(lp, x, cos, sin, cfg, policy)
                return (x, aux_acc + aux), stats

            xs = ll
        else:
            # grouped interleave on the LOCAL slice (see _grouped_scan)
            xs, body = _grouped_scan(cfg, ll, cos, sin, policy)

        (x, aux_sum), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs
        )
        return x, aux_sum

    def loss_fn(params, y, mb):
        h = norm_ops.apply_rms_norm(params["final_norm"], y, eps=lc.rms_norm_eps)
        logits = llama.logits_fn(params, h, lc, policy)
        labels = mb["labels"]
        loss_mask = mb.get("loss_mask")
        if shift_labels:
            logits, labels, loss_mask = ce_ops.shift_for_next_token(
                logits, labels, loss_mask
            )
        loss_sum = ce_ops.cross_entropy_loss(
            logits, labels, loss_mask=loss_mask, reduction="sum"
        )
        valid = (labels != -100).astype(jnp.float32)
        if loss_mask is not None:
            valid = valid * loss_mask.astype(jnp.float32)
        return loss_sum, jnp.sum(valid)

    return embed_fn, stage_fn, loss_fn


def forward(
    params,
    batch: dict[str, jax.Array],
    cfg: MixtralConfig,
    policy: DtypePolicy,
    *,
    shift_labels: bool = True,
    return_logits: bool = False,
):
    """Causal-LM forward -> (loss, aux).  Adds ``router_aux_loss_coef`` x mean
    per-layer load-balancing loss (reference ``modeling_mixtral.py:872-878``)."""
    lc = cfg.llama
    input_ids = batch["input_ids"]
    attention_mask = batch.get("attention_mask")
    segment_ids = batch.get("segment_ids")
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype
    )
    x = shd.constrain(x, aspec)
    cos, sin = llama._rope_for(
        input_ids, lc,
        positions=llama.positions_for(input_ids, attention_mask, segment_ids)
    )
    layer_stack = params["layers"]
    if cfg.moe_frequency == 1:

        def body(carry, lp):
            x, aux_acc = carry
            lp = _cast_layer(lp, policy)
            x, aux, stats = _decoder_layer(lp, x, cos, sin, cfg, policy,
                                           attention_mask=attention_mask,
                                           segment_ids=segment_ids)
            return (x, aux_acc + aux), stats

        xs = layer_stack
    else:
        # grouped interleave: scan over [L/f] groups of (MoE + f-1 dense)
        xs, body = _grouped_scan(cfg, layer_stack, cos, sin, policy,
                                 attention_mask=attention_mask,
                                 segment_ids=segment_ids)

    body = llama.checkpoint_layer(body, lc, stack="layers")
    (x, aux_sum), stats = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    # router_aux_loss is already coefficient-weighted (weighted_router_loss);
    # averaged over the layers that HAVE routers
    aux: dict[str, Any] = {"router_aux_loss": aux_sum / num_moe_layers(cfg)}
    # the expert blocks' scalars (moe/...), the largest over the layers
    aux.update({name: jnp.max(v) for name, v in stats.items()})
    with jax.named_scope("ce_head"):
        hidden = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
        logits = llama.logits_fn(params, hidden, lc, policy)
        if return_logits:
            aux["logits"] = logits
        labels = batch.get("labels")
        if labels is None:
            return logits, aux
        loss_mask = batch.get("loss_mask")
        if attention_mask is not None:
            am = attention_mask.astype(jnp.float32)
            loss_mask = am if loss_mask is None else loss_mask * am
        if shift_labels:
            logits, labels, loss_mask = ce_ops.shift_for_next_token(logits, labels, loss_mask)
        lm_loss = ce_ops.cross_entropy_loss(logits, labels, loss_mask=loss_mask)
    loss = lm_loss + aux["router_aux_loss"]
    aux["lm_loss"] = lm_loss
    return loss, aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def _loss(cfg: MixtralConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    if cfg.llama.attention_impl == "zigzag_ring":
        # the zig-zag batch/position transform is llama's loss's; running the
        # op on an unpermuted batch would silently corrupt the causal structure
        raise NotImplementedError(
            "zigzag_ring_attention is llama/mistral-only; use "
            "fusions.ring_attention for mixtral"
        )
    return lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)


def _logits(cfg: MixtralConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, aux = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, aux["router_aux_loss"]

    return fwd


def _pipeline(cfg: MixtralConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    # the router loss is normalized over the layers that HAVE routers
    return (pipeline_hooks(cfg, policy, shift_labels=shift_labels),
            {"stage_aux": True, "aux_inv_layers": 1.0 / num_moe_layers(cfg)})


def _decode():
    from neuronx_distributed_training_tpu.models import decode

    return decode.prefill_mixtral, decode.decode_step_mixtral


def _flops_breakdown(cfg: MixtralConfig, seq_len: int) -> dict[str, float]:
    lc = cfg.llama
    n_moe = num_moe_layers(cfg)
    swiglu = 2 * lc.hidden_size * 3 * lc.intermediate_size
    return {
        **llama.flops_breakdown(lc, seq_len),
        "mlp": (lc.num_layers - n_moe) * swiglu + n_moe * cfg.moe.top_k * swiglu,
        "router": float(n_moe * 2 * lc.hidden_size * cfg.moe.num_experts),
    }


FAMILY = Family(
    name="mixtral",
    config_from=MixtralConfig.from_config,
    loss=_loss,
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=_flops_breakdown,
    plan_shape=lambda cfg: {
        **llama.plan_shape(cfg.llama), "num_experts": int(cfg.moe.num_experts),
        "top_k": int(cfg.moe.top_k), "moe_frequency": int(cfg.moe_frequency or 1)},
    logits=_logits,
    head=lambda cfg, policy, **kw: llama.head(cfg.llama, policy, **kw),
    pipeline=_pipeline,
    # The head wiring would be llama's (the same top-level layout:
    # ``lambda cfg, policy: llama.onef1b_head_hooks(cfg.llama, policy)``), but
    # the sort-based dropless-MoE stage vjp read a few percent off inside the
    # manual tick loop when last bisected (loss exact; dense stages exact under
    # the same schedule), so mixtral keeps the autodiff wavefront until that
    # is retested (ROADMAP M7)
    onef1b_head=Refused(
        "mixtral: dropless-MoE stage vjp has backend-dependent numerics "
        "under the 1f1b tick loop (dense families only for now)"),
    decode=_decode,
    moe_groups=lambda cfg: num_moe_layers(cfg) if cfg.moe_frequency != 1 else None,
)
