"""Ouro-family looped decoder (arXiv:2510.25741, ``model_type: ouro``).

One Llama-shaped decoder stack applied ``total_ut_steps`` times with the SAME
weights; the final norm, the language-model head and a scalar exit gate at the
end of every pass; a second RMSNorm on each sub-layer's output ("sandwich"
norms).  The pre-training objective mixes the passes' per-token
cross-entropies by the exit distribution the gates define, less an entropy
term (uniform prior over exit steps)::

    h_0 = E[x]
    for t = 1..T:  h_t = N_f(stack(h_{t-1}));  ce_t = CE(h_t W_head);  g_t = sigmoid(h_t . w_g + b_g)
    p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j);  p_T = prod_{j<T} (1 - g_j)
    loss = mean_i [ sum_t p_t[i] ce_t[i]  -  beta * H(p[i]) ]

The attention and MLP bodies, the layer scan and its recomputation are
``models.llama``'s (``decoder_stack`` with ``post_sublayer_norms``); this file
adds the loop over passes, the per-pass head and the exit mixture.  Parameters
scale with layers, activations with layers x passes: under an
``activations_checkpoint_granularity`` the scan over passes emits only
``[T, batch, seq]`` floats (``ce_t`` and the gate's logit) and backward holds
one pass's logits at a time: under ``selective`` the whole pass (stack, final
norm, head, CE, gate) is rematerialized and its layers keep their residuals
once; under ``full`` the layers keep their inputs and the flash kernel's
outputs (``llama._remat_policy``) and the head alone is rematerialized.
Either way the stack runs forward twice, not three times.
``T = 1`` without the post-sub-layer norms is ``llama.forward``.

Not wired (each refused by name): pipeline parallelism (a pass would circle
the stages), ``models/decode.py`` / ``generate.py`` (a KV cache per pass),
preference alignment, LoRA on the gate, and the later training stage that
freezes the model and fits the gate alone.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import Family, Refused
from neuronx_distributed_training_tpu.ops import cross_entropy as ce_ops
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Llama knobs + the loop: ``total_ut_steps`` passes over one stack and
    the entropy weight of the exit objective."""

    llama: llama.LlamaConfig = dataclasses.field(
        default_factory=lambda: llama.LlamaConfig(post_sublayer_norms=True))
    total_ut_steps: int = 4
    exit_entropy_beta: float = 0.1

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
        return self.llama.head_dim

    @property
    def family(self) -> Family:
        return FAMILY

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the loop is not wired for, each by
        # its key's name (``config.loader.validate_config`` calls every
        # family's ``config_from``)
        m = dict(model_cfg or {})
        passes = int(m.get("total_ut_steps", 4))
        if passes < 1:
            raise ValueError(f"model.total_ut_steps must be >= 1, got {passes}")
        if int((ds_cfg or {}).get("pipeline_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "distributed_strategy.pipeline_model_parallel_size > 1 is not "
                "wired for model.architecture: ouro: every pass would have to "
                "circle the stages (parallel/pipeline.py has no circular pass "
                "over a shared stack)")
        lora_targets = (m.get("lora") or {}).get("target_modules") or ()
        if any("exit_gate" in str(t) for t in lora_targets):
            raise ValueError(
                "model.lora.target_modules names exit_gate: the exit gate is a "
                "multiply-reduce in float32, not a linear layer an adapter "
                "can wrap")
        base = dataclasses.replace(
            llama.LlamaConfig.from_config(m, ds_cfg), post_sublayer_norms=True)
        return cls(llama=base, total_ut_steps=passes,
                   exit_entropy_beta=float(m.get("exit_entropy_beta", 0.1)))


def init_params(key: jax.Array, cfg: OuroConfig, policy: DtypePolicy | None = None):
    """``llama.init_params`` (four norms a layer) + the exit gate,
    ``Linear(hidden -> 1)`` with a zero bias."""
    policy = policy or DtypePolicy()
    params = llama.init_params(key, cfg.llama, policy)
    params["exit_gate"], _ = linear_ops.init_linear(
        jax.random.fold_in(key, 777), cfg.hidden_size, 1, shard="replicated",
        dtype=policy.param_dtype, stddev=cfg.llama.initializer_range, use_bias=True)
    return params


def param_specs(cfg: OuroConfig, *, pipeline: bool = False):
    specs = llama.param_specs(cfg.llama, pipeline=pipeline)
    specs["exit_gate"] = {"w": P(None, None), "bias": P(None)}
    return specs


def exit_distribution(gate_logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``gate_logits [T, ...]`` -> ``(p [T, ...], entropy [...])``: the
    probability of leaving after pass ``t`` (the last pass takes what is left;
    its own gate is unused).  ``p`` is the plain product of gates, which sums
    to 1 by telescoping whatever ``sigmoid`` returns; ``log p`` for the entropy
    is summed in logs, so that a saturated gate gives ``0 x finite = 0`` and
    not a NaN."""
    z = gate_logits[:-1]
    zero = jnp.zeros((1,) + z.shape[1:], z.dtype)
    g = jax.nn.sigmoid(z)
    p = (jnp.concatenate([g, zero + 1.0], axis=0)
         * jnp.concatenate([zero + 1.0, jnp.cumprod(1.0 - g, axis=0)], axis=0))
    log_p = (jnp.concatenate([zero, jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)], axis=0)
             + jnp.concatenate([jax.nn.log_sigmoid(z), zero], axis=0))
    return p, -jnp.sum(p * log_p, axis=0)


def forward(
    params,
    batch: dict[str, jax.Array],
    cfg: OuroConfig,
    policy: DtypePolicy,
    *,
    shift_labels: bool = True,
):
    """Causal-LM forward of the looped stack -> ``(loss, aux)``; ``aux``
    holds the per-pass losses and the exit distribution's token means
    (``loss/ce_pass<t>``, ``exit/p_pass<t>``, ``exit/entropy``)."""
    lc = cfg.llama
    attention_mask = batch.get("attention_mask")
    segment_ids = batch.get("segment_ids")
    labels = batch["labels"]
    loss_mask = batch.get("loss_mask")
    # the exit mixture, like the softmax's internals, is worked in the
    # policy's softmax dtype (float32 in every regime)
    wide = policy.softmax_dtype
    if attention_mask is not None:
        am = attention_mask.astype(wide)
        loss_mask = am if loss_mask is None else loss_mask * am
    if shift_labels:
        labels = labels[:, 1:]
        loss_mask = None if loss_mask is None else loss_mask[:, 1:]
    head_plain = "lora_a" not in params.get("lm_head", {})
    x, cos, sin = llama.embed_and_rope(
        params, batch["input_ids"], lc, policy,
        attention_mask=attention_mask, segment_ids=segment_ids)

    def head(h):
        # scope names: telemetry.spans.DEVICE_SCOPES, and "exit_gate" inside "ce_head"
        with jax.named_scope("ce_head"):
            h = norm_ops.apply_rms_norm(params["final_norm"], h, eps=lc.rms_norm_eps)
            seen = h[:, :-1] if shift_labels else h
            if lc.vocab_chunks and head_plain:
                head_w = (params["embed"]["embedding"].T if lc.tie_word_embeddings
                          else params["lm_head"]["w"])
                ce = ce_ops.chunked_cross_entropy_from_hidden(
                    seen, head_w, labels, num_chunks=lc.vocab_chunks,
                    loss_mask=loss_mask, reduction="none")
            else:
                logits = llama.logits_fn(params, h, lc, policy)
                if shift_labels:
                    logits = logits[:, :-1, :]
                ce = ce_ops.cross_entropy_loss(
                    logits, labels, loss_mask=loss_mask, reduction="none")
            with jax.named_scope("exit_gate"):
                if cfg.total_ut_steps == 1:   # the last pass's gate is unused
                    z = jnp.zeros(ce.shape, wide)
                else:
                    # a wide multiply-reduce, not a one-column matmul
                    gate = params["exit_gate"]
                    z = jnp.sum(seen.astype(wide) * gate["w"][:, 0].astype(wide), axis=-1)
                    z = z + gate["bias"].astype(wide)
        return h, (ce, z)

    def one_pass(h, _):
        h = llama.decoder_stack(params["layers"], h, cos, sin, lc, policy,
                                attention_mask=attention_mask,
                                segment_ids=segment_ids)
        return head(h)

    # What backward keeps of a pass: its carry and [batch, seq] floats, and one
    # pass's logits at a time.  Layers that keep only their inputs ("full")
    # leave the head to rematerialize; layers that keep their residuals
    # ("selective") are themselves held once a pass, so the whole pass is.
    if lc.activations_checkpoint_granularity == "full":
        head = jax.checkpoint(head, prevent_cse=False)
    elif lc.activations_checkpoint_granularity is not None:
        one_pass = jax.checkpoint(one_pass, prevent_cse=False)
    _, (ce, z) = jax.lax.scan(one_pass, x, None, length=cfg.total_ut_steps)

    with jax.named_scope("ce_head"), jax.named_scope("exit_gate"):
        mask = (labels != -100).astype(wide)
        if loss_mask is not None:
            mask = mask * loss_mask.astype(wide)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        p, entropy = exit_distribution(z)
        # ce is already masked; the entropy term is masked here
        per_tok = jnp.sum(p * ce, axis=0) - cfg.exit_entropy_beta * entropy * mask
        loss = jnp.sum(per_tok) / denom
        aux: dict[str, Any] = {"exit/entropy": jnp.sum(entropy * mask) / denom}
        for t in range(cfg.total_ut_steps):
            aux[f"loss/ce_pass{t + 1}"] = jnp.sum(ce[t]) / denom
            aux[f"exit/p_pass{t + 1}"] = jnp.sum(p[t] * mask) / denom
    return loss, aux


# the family's record (models/family.py)
FAMILY = Family(
    name="ouro",
    config_from=OuroConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=lambda cfg, seq_len: llama.flops_breakdown(
        cfg.llama, seq_len, passes=cfg.total_ut_steps),
    # llama's layout: the planner prices the stack's memory as applied once
    # and sees the passes through ``flops_breakdown`` only
    plan_shape=lambda cfg: llama.plan_shape(cfg.llama),
    logits=Refused("preference alignment not wired for OuroConfig"),
    head=Refused("a single head not wired for OuroConfig: there is one at the end of every pass"),
    pipeline=Refused(
        "pipeline parallelism not wired for OuroConfig yet: every pass would "
        "have to circle the stages"),
    onef1b_head=Refused(
        "OuroConfig: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=Refused(
        "model.architecture: ouro (total_ut_steps passes over one stack) "
        "has no cached decode: every pass needs a KV cache of its own "
        "(models/decode.py holds one per layer)"),
    run_facts=lambda cfg, sched: {
        "loop_passes": int(cfg.total_ut_steps),
        "layer_applications_per_step": (
            int(cfg.total_ut_steps) * int(cfg.num_layers)
            * int(sched["num_microbatches"]))},
)
