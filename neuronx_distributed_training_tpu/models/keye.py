"""Keye-VL-2 language decoder (``model_type: KeyeVL2``; the language model of
huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B): every layer is grouped-query
attention whose keys a learned indexer chooses, then routed experts.

    x'  = x + Attn(RMS(x))          (``input_norm``)
    x'' = x' + MoE(RMS(x'))         (``post_attn_norm``)
    after the last layer ``final_norm``, then the untied head

- attention, ``h = RMS(x)``: ``[q ; k ; v] = h Wqkv``, ``num_attention_heads``
  / ``num_key_value_heads`` heads of ``head_dim``; an RMS norm on every head's
  q and k (one learned scale of ``head_dim`` for all heads: the leaves
  ``q_norm`` / ``k_norm``), rotate-half rope at ``rope_theta`` on all the
  head's dims, scores ``q . k / sqrt(head_dim)``.  The softmax of query ``t``
  runs over the set ``S_t`` the indexer chose (``ops/sparse_attention.py`` has
  the indexer, the selection, the masked attention and the indexer's own loss
  ``L_I``; ``sa_config`` its sizes: ``indexer_num_heads`` index heads of
  ``indexer_head_dim``, one index key a token, ``topk`` keys a query).  The
  indexer reads the same ``h``, detached; its leaves (``attn/indexer/...``:
  ``wq``, ``wk``, ``weights``, the key's LayerNorm ``k_norm``) stay float32
  and get their gradient from ``L_I`` alone, and no other leaf gets any from
  it.
- experts, ``g = RMS(x')``: ``P = softmax(g Wr)`` over ``num_experts`` in
  float32, the ``num_experts_per_tok`` largest, their weights renormalised
  (``norm_topk_prob``); SwiGLU experts of ``moe_intermediate_size``; no shared
  expert; the routers' load-balancing loss (``router_aux_loss_coef``, the mean
  over the layers as ``models/mixtral.py``).  ``num_experts_held: [lo, hi]``
  makes the program one chip of an expert-parallel deployment, alone
  (``ops.moe._held_experts``).

What ``fit()`` minimises: cross entropy + the routers' loss + ``sum_layers
L_I`` (coefficient 1 a layer); the three are logged apart
(``lm_loss``, ``router_aux_loss``, ``dsa/indexer_loss``; ``dsa/indexer_kl``
is the mean ``L_I`` a layer and ``dsa/kept_pairs_share`` the selected pairs
over the visible ones, the mean over the layers).

Scopes: the whole first half under ``attention``; inside it ``qk_norm``,
``indexer`` (the three projections, the LayerNorm, the rope, the index
scores), ``select`` (threshold and mask) and ``indexer_loss`` (``p``, the KL
and their backward) (``telemetry.spans.FAMILY_SCOPES``).

``attention_mask`` (left padding) and ``segment_ids`` (packed documents) reach
the selection: a key outside the query's document, or a padded one, is never
chosen.

Not wired (each refused by name): pipeline stages (``L_I`` and the routers'
loss would cross stages as one more scalar each; nothing threads them), tensor
parallelism (the index heads and the one index key are not laid out over the
model axis), context and sequence parallelism (a shard's queries select among
every earlier shard's index keys, which nothing gathers), cached decode (a
cache of index keys beside keys and values), a held range together with expert
parallelism, the ring and all-to-all attention ops (their kernels mask by
rule; the selection is data: ``fusions.flash_attention`` asks for the family's
own masked kernels, ``ops/sparse_attention.py``); ``tools/convert.py`` does not know the family's leaves; the vision
tower is not built, and the three position streams of ``mrope_section`` are
equal on text, so the rope is the plain one.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import Family, Refused
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.ops import sparse_attention as sa_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """Llama knobs (``llama``: widths, rope, recomputation) + the routed block
    (``moe``) + the learned selection (``sa``)."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    sa: sa_ops.SparseAttentionConfig = dataclasses.field(
        default_factory=sa_ops.SparseAttentionConfig)
    moe_intermediate_size: int = 768

    # architecture passthroughs (perf estimation, data-module sizing)
    @property
    def vocab_size(self) -> int:
        return self.llama.vocab_size

    @property
    def hidden_size(self) -> int:
        return self.llama.hidden_size

    @property
    def intermediate_size(self) -> int:
        return self.moe_intermediate_size

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

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the family is not wired for, each
        # by its key's name
        m, ds = dict(model_cfg or {}), dict(ds_cfg or {})
        arch = "model.architecture: keye"
        scaling = m.get("rope_scaling") or {}
        if str(scaling.get("rope_type", scaling.get("type", "default"))) != "default":
            raise ValueError(f"model.rope_scaling {scaling}: wired for {arch} is the "
                             "default rope (mrope_section is unread: on text its three "
                             "position streams are equal)")
        base = dataclasses.replace(
            llama.LlamaConfig.from_config(m, ds),
            rms_norm_eps=float(m.get("rms_norm_eps", 1e-6)),
            rope_theta=float(m.get("rope_theta", 1e7)),
            head_dim=int(m["head_dim"]) if m.get("head_dim") is not None else None,
            # the source's: a ``sliding_window`` is unread unless ``use_sliding_window``
            sliding_window=None)
        if base.attention_impl not in ("core", "flash"):
            raise ValueError(
                f"model.fusions selects the {base.attention_impl} attention op, which is "
                f"not wired for {arch}: those kernels mask by rule (causal, window, "
                "segment, padding) and the indexer's selection is data; "
                "model.fusions.flash_attention asks for the family's own masked kernels "
                "(ops/sparse_attention.py)")
        if base.tie_word_embeddings:
            raise ValueError(f"model.tie_word_embeddings: true is not wired for {arch} "
                             "(the head is its own leaf)")
        if bool(m.get("use_sliding_window", False)):
            raise ValueError(f"model.use_sliding_window: true is not wired for {arch}: the "
                             "keys a query sees are the indexer's choice")
        if base.num_attention_heads % base.kv_heads:
            raise ValueError(f"model.num_attention_heads {base.num_attention_heads} is no "
                             f"multiple of num_key_value_heads {base.kv_heads}")
        if base.fuse_qkv is False:
            raise ValueError(f"model.fuse_qkv: false is not wired for {arch}")
        if list(m.get("mlp_only_layers") or []) or int(m.get("decoder_sparse_step", 1)) != 1:
            raise ValueError(f"model.mlp_only_layers / decoder_sparse_step: {arch} has "
                             "routed experts in every layer")
        keys = ("the index keys of every earlier shard would have to be gathered for the "
                "selection, and the mask laid out over the shards; nothing does")
        for key, why in (
                ("pipeline_model_parallel_size",
                 "the indexer's loss and the routers' would cross stages as scalars "
                 "nothing threads"),
                ("tensor_model_parallel_size",
                 "the index heads and the one index key are not laid out over the "
                 "model axis"),
                ("context_parallel_size", keys)):
            if int(ds.get(key, 1) or 1) > 1:
                raise ValueError(f"distributed_strategy.{key} > 1 is not wired for "
                                 f"{arch}: {why}")
        if bool(ds.get("sequence_parallel", False)):
            raise ValueError(f"distributed_strategy.sequence_parallel is not wired for "
                             f"{arch}: {keys}")
        experts = int(m.get("num_experts", 0) or 0)
        if experts < 2:
            raise ValueError(f"model.num_experts {experts}: {arch} has routed experts in "
                             "every layer")
        held = m.get("num_experts_held")
        if held is not None and int(ds.get("expert_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "model.num_experts_held with distributed_strategy."
                "expert_model_parallel_size > 1: a held range is one chip's share "
                "of the experts, with no peer to exchange with (ops/moe.py)")
        if held is not None and not 0 <= int(held[0]) < int(held[1]) <= experts:
            raise ValueError(f"model.num_experts_held {held}: want 0 <= lo < hi <= "
                             f"num_experts {experts}")
        moe = moe_ops.MoEConfig.from_config({
            "num_experts": experts, "top_k": int(m.get("num_experts_per_tok", 8)),
            "dropless": True,
            "router_aux_loss_coef": float(m.get("router_aux_loss_coef", 0.001)),
            "normalize_top_k_affinities": bool(m.get("norm_topk_prob", True)),
            "experts_held": held})
        return cls(
            llama=base, moe=moe,
            sa=sa_ops.SparseAttentionConfig.from_config(
                m.get("sa_config"), norm_eps=base.rms_norm_eps,
                way="flash_mask" if base.attention_impl == "flash" else "xla_chunks"),
            moe_intermediate_size=int(m.get("moe_intermediate_size", 768)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: KeyeConfig, dtype):
    lc = cfg.llama
    ks = jax.random.split(key, 8)
    h, d, nh, nkv, std = (lc.hidden_size, lc.head_size, lc.num_attention_heads, lc.kv_heads,
                          lc.initializer_range)

    def linear(k, n_in, n_out):
        return linear_ops.init_linear(k, n_in, n_out, shard="replicated", dtype=dtype,
                                      stddev=std)[0]

    return {
        "input_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "post_attn_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "attn": {"qkv": linear(ks[0], h, (nh + 2 * nkv) * d),
                 "q_norm": norm_ops.init_rms_norm(d, dtype=dtype)[0],
                 "k_norm": norm_ops.init_rms_norm(d, dtype=dtype)[0],
                 "o": linear(ks[3], nh * d, h),
                 "indexer": sa_ops.init_indexer(ks[5], h, cfg.sa, dtype=dtype, stddev=std)},
        "mlp": moe_ops.init_moe_params(ks[7], h, cfg.moe_intermediate_size, cfg.moe,
                                       dtype=dtype, stddev=std)}


def init_params(key: jax.Array, cfg: KeyeConfig, policy: DtypePolicy | None = None):
    """``embed``, ``layers`` (one stack, layer ``i`` from the ``i``-th of the
    layers' keys), ``final_norm``, ``lm_head``."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    kemb, klayers, khead = jax.random.split(key, 3)
    return {
        "embed": linear_ops.init_embedding(
            kemb, lc.vocab_size, lc.hidden_size, dtype=dtype, stddev=lc.initializer_range)[0],
        "layers": jax.vmap(lambda k: _init_layer(k, cfg, dtype))(
            jax.random.split(klayers, lc.num_layers)),
        "final_norm": norm_ops.init_rms_norm(lc.hidden_size, dtype=dtype)[0],
        "lm_head": linear_ops.init_linear(
            khead, lc.hidden_size, lc.vocab_size, shard="column", dtype=dtype,
            stddev=lc.initializer_range)[0],
    }


def param_specs(cfg: KeyeConfig, *, pipeline: bool = False):
    """PartitionSpec tree of ``init_params``: the vocabulary over ``model`` as
    llama's; the layers replicated but for the expert dim over ``expert``
    where the experts are all held (tp is refused at the config)."""
    if pipeline:
        raise NotImplementedError(FAMILY.pipeline.sentence)
    w2 = {"w": P(None, None, None)}   # every leaf leads with the layers
    scale = {"scale": P(None, None)}
    w3 = P(None, None if cfg.moe.experts_held is not None else "expert", None, None)
    return {
        "embed": {"embedding": P("model", None)},
        "layers": {
            "input_norm": scale, "post_attn_norm": scale,
            "attn": {"qkv": w2, "q_norm": scale, "k_norm": scale, "o": w2,
                     "indexer": {"wq": w2, "wk": w2, "weights": w2,
                                 "k_norm": {"scale": P(None, None), "bias": P(None, None)}}},
            "mlp": {"router": w2, "experts": {"gate_up": w3, "down": w3}}},
        "final_norm": {"scale": P(None)},
        "lm_head": {"w": P(None, "model")},
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _cast_layer(lp, policy: DtypePolicy):
    """The per-layer cast to the compute dtype of all but the expert weights
    (``models/mixtral.py``) and the indexer's leaves, whose scores are float32."""
    cast = policy.cast_to_compute(lp)
    return {**cast, "attn": {**cast["attn"], "indexer": lp["attn"]["indexer"]},
            "mlp": {**cast["mlp"], "experts": lp["mlp"]["experts"]}}


def _attention_block(lp, x, rope, index_rope, cfg: KeyeConfig, policy: DtypePolicy,
                     attention_mask=None, segment_ids=None):
    """``x`` (already normed) through qkv, the head norms, the rope, the
    indexer, the selected attention and ``o`` -> ``(out, the op's stats)``."""
    lc = cfg.llama
    b, s, _ = x.shape
    nh, nkv, d = lc.num_attention_heads, lc.kv_heads, lc.head_size
    qkv = linear_ops.apply_linear(lp["qkv"], x)
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    q, k, v = q.reshape(b, s, nh, d), k.reshape(b, s, nkv, d), v.reshape(b, s, nkv, d)
    with jax.named_scope("qk_norm"):
        q = norm_ops.apply_rms_norm(lp["q_norm"], q, eps=lc.rms_norm_eps)
        k = norm_ops.apply_rms_norm(lp["k_norm"], k, eps=lc.rms_norm_eps)
    q, k = rope_ops.apply_rope(q, *rope), rope_ops.apply_rope(k, *rope)
    with jax.named_scope("indexer"):
        qi, ki, wi = sa_ops.indexer_inputs(lp["indexer"], x, *index_rope, cfg.sa)
    out, stats = sa_ops.sparse_attention(
        q, k, v, qi, ki, wi, cfg.sa, attention_mask=attention_mask,
        segment_ids=segment_ids, softmax_dtype=policy.softmax_dtype,
        compute_dtype=policy.compute_dtype)
    return linear_ops.apply_linear(lp["o"], out.reshape(b, s, nh * d)), stats


def _decoder_layer(lp, x, rope, index_rope, cfg: KeyeConfig, policy: DtypePolicy,
                   attention_mask=None, segment_ids=None):
    """One layer -> ``(x, the losses [2]: the router's (weighted) and L_I,
    stats)``."""
    lc = cfg.llama
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES and FAMILY_SCOPES
    with jax.named_scope("attention"):
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=lc.rms_norm_eps)
        hidden, sa_stats = _attention_block(
            lp["attn"], hidden, rope, index_rope, cfg, policy,
            attention_mask=attention_mask, segment_ids=segment_ids)
        x = shd.constrain(x + hidden, aspec)
    # moe_block opens the "moe" scope itself; the norm before it and the router
    # loss and residual after it belong with it (as models/mixtral.py)
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec)
    with jax.named_scope("moe"):
        router_loss = moe_ops.weighted_router_loss(
            aux["router_logits"], aux["expert_idx"], cfg.moe)
        x = shd.constrain(x + hidden, aspec)
        with jax.named_scope("router"):
            counts = moe_ops.expert_counts(aux["expert_idx"], cfg.moe.num_experts)
    stats = {**aux["stats"],
             "moe/load_max_share": jnp.max(counts) / jnp.mean(counts),
             "dsa/kept_pairs_share": sa_stats["kept_pairs"] / sa_stats["causal_pairs"]}
    return x, jnp.stack([router_loss, sa_stats["kl"]]), stats


def forward(params, batch: dict[str, jax.Array], cfg: KeyeConfig, policy: DtypePolicy, *,
            shift_labels: bool = True, return_logits: bool = False):
    """Causal-LM forward -> ``(loss, aux)``: cross entropy + the routers' loss
    (the mean over the layers) + the sum of the layers' ``L_I``, each also in
    ``aux``."""
    lc = cfg.llama
    input_ids = batch["input_ids"]
    attention_mask, segment_ids = batch.get("attention_mask"), batch.get("segment_ids")
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, shd.act_spec(lc.sequence_parallel, lc.context_parallel))
    positions = llama.positions_for(input_ids, attention_mask, segment_ids)

    def rope_of(dim):
        return rope_ops.rope_cos_sin(
            positions, rope_ops.rope_frequencies(dim, theta=lc.rope_theta), dtype=jnp.float32)

    rope, index_rope = rope_of(lc.head_size), rope_of(cfg.sa.index_head_dim)

    def body(carry, lp):
        x, losses = carry
        x, layer_losses, stats = _decoder_layer(
            _cast_layer(lp, policy), x, rope, index_rope, cfg, policy,
            attention_mask=attention_mask, segment_ids=segment_ids)
        return (x, losses + layer_losses), stats

    # ``full`` keeps ``L_I``'s gradient too: the rerun forms nothing of the loss
    body = llama.checkpoint_layer(body, lc, stack="layers", kept=sa_ops.KEPT_NAMES)
    (x, losses), stats = jax.lax.scan(
        body, (x, jnp.zeros((2,), jnp.float32)), params["layers"])
    aux: dict[str, Any] = {
        "router_aux_loss": losses[0] / lc.num_layers,
        "dsa/indexer_loss": losses[1],
        "dsa/indexer_kl": losses[1] / lc.num_layers,
        "dsa/kept_pairs_share": jnp.mean(stats.pop("dsa/kept_pairs_share"))}
    # the expert blocks' scalars (moe/...), the largest over the layers
    aux.update({name: jnp.max(v) for name, v in stats.items()})
    with jax.named_scope("ce_head"):
        hidden = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
        out, head_aux = llama._head_loss(params, hidden, batch, lc, policy,
                                         shift_labels=shift_labels,
                                         return_logits=return_logits)
    aux.update(head_aux)
    if batch.get("labels") is None:
        return out, aux
    aux["lm_loss"] = out
    return out + aux["router_aux_loss"] + aux["dsa/indexer_loss"], aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def flops_breakdown(cfg: KeyeConfig, seq_len: int) -> dict[str, float]:
    """``Family.flops_breakdown``: REQUIRED work.  Under ``attention`` the
    projections, the main scores and values of the selected pairs alone (a
    query at ``t`` keeps ``min(topk, t + 1)`` keys), and the indexer: its three
    projections and the index scores of every causal pair.  Of the routed
    experts only the slots this program holds."""
    lc, sa = cfg.llama, cfg.sa
    h, d, nh, nkv = lc.hidden_size, lc.head_size, lc.num_attention_heads, lc.kv_heads
    k = min(sa.topk, seq_len)
    kept = (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len   # pairs a token, the mean
    index_dims = sa.index_heads * sa.index_head_dim
    attention = (2 * h * (nh + 2 * nkv) * d + 2 * nh * d * h + 4 * nh * d * kept
                 + 2 * h * (index_dims + sa.index_head_dim + sa.index_heads) * 2 / 3
                 + 2 * index_dims * (seq_len + 1) / 2)
    slots = cfg.moe.top_k * cfg.moe.experts_resident / cfg.moe.num_experts
    return {
        "attention": float(lc.num_layers * attention),
        "mlp": float(lc.num_layers * 6 * h * cfg.moe_intermediate_size * slots),
        "router": float(lc.num_layers * 2 * h * cfg.moe.num_experts),
        "head": 2.0 * h * lc.vocab_size,
    }


def _run_facts(cfg: KeyeConfig, sched) -> dict:
    # ``sparse_attention`` (its sizes and the way taken) is a fact of the
    # trace: ``ops.sparse_attention.sparse_attention`` records it
    facts: dict[str, Any] = {"moe_score_func": cfg.moe.score_func}
    if cfg.moe.experts_held is not None:
        facts["moe_experts_held"] = [*cfg.moe.experts_held, cfg.moe.num_experts]
    return facts


def _logits(cfg: KeyeConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, aux = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, aux["router_aux_loss"] + aux["dsa/indexer_loss"]

    return fwd


FAMILY = Family(
    name="keye",
    config_from=KeyeConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    # llama's layout with the experts' width for the MLP: the planner prices
    # neither the indexer nor the selection
    plan_shape=lambda cfg: {
        **llama.plan_shape(dataclasses.replace(
            cfg.llama, intermediate_size=cfg.moe_intermediate_size)),
        "num_experts": int(cfg.moe.num_experts), "top_k": int(cfg.moe.top_k),
        "moe_frequency": 1},
    logits=_logits,
    head=lambda cfg, policy, **kw: llama.head(cfg.llama, policy, **kw),
    pipeline=Refused(
        "pipeline parallelism not wired for KeyeConfig: the indexer's loss and the "
        "routers' would cross stages as scalars nothing threads"),
    onef1b_head=Refused(
        "KeyeConfig: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=Refused(
        "model.architecture: keye has no cached decode: a step would need a cache of "
        "index keys beside keys and values, and the selection over it "
        "(models/decode.py holds keys and values alone)"),
    run_facts=_run_facts,
)
