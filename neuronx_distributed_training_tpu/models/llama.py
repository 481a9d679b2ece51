"""Llama-family decoder, TPU-native.

Functional re-design of the reference's ``models/hf_models/modeling_llama.py``
(873 LoC of NxD-parallel ``nn.Module``s): the same architecture — vocab-sharded
embedding, fused-QKV or GQA attention with RoPE, fused gate/up SwiGLU MLP,
RMSNorm, no-gather lm_head + vocab-parallel cross-entropy — expressed as pure
functions over a parameter pytree:

- layers are *stacked* (leading ``[num_layers, ...]`` dim) and executed with
  ``jax.lax.scan`` — one compiled block regardless of depth (compile time and
  HLO size independent of num_layers, and the natural substrate for pipeline
  stage splitting later);
- TP/SP/CP are PartitionSpecs (see ``parallel/sharding.py``), not wrapper
  modules: what the reference does with ColumnParallel/RowParallel layers and
  explicit scatter/gather (``modeling_llama.py:296-357``, ``:398-400``) GSPMD
  derives from the weight/activation specs;
- activation checkpointing maps the reference's
  ``activations_checkpoint_granularity: selective|full`` +
  ``activations_checkpoint_recompute: [CoreAttention]``
  (``hf_llama3_8B_config.yaml:76-93``) onto ``jax.checkpoint`` policies over the
  scanned block: "selective" saves everything except tagged attention
  internals, "full" saves nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models.family import Family
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import cross_entropy as ce_ops
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy
from neuronx_distributed_training_tpu.utils.perf import _attention_flops_per_token


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Architecture + parallel-behavior knobs, mirroring the reference's
    ``model:`` YAML block + HF ``config.json`` fields (``llama_model.py:24-74``)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_attention_heads: int = 32
    num_kv_heads: Optional[int] = None  # None -> MHA
    head_dim: Optional[int] = None
    max_position_embeddings: int = 8192
    rope_theta: float = 10000.0
    rope_interpolation_factor: Optional[float] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    sliding_window: Optional[int] = None
    # a second RMSNorm on each sub-layer's OUTPUT, before the residual add
    # (leaves ``input_norm_2`` / ``post_attn_norm_2``); set by the families
    # built on this block that have them (models/ouro.py), no YAML key
    post_sublayer_norms: bool = False
    # parallel / fusion behavior
    fuse_qkv: bool = True
    attention_impl: str = "core"  # "core" | "flash" | "ring" | "ulysses"
    flash_block_q: Optional[int] = None   # Pallas tile override (perf tuning)
    flash_block_kv: Optional[int] = None
    vocab_chunks: Optional[int] = None    # fusions.chunked_ce: fused head+CE
    sequence_parallel: bool = False
    context_parallel: bool = False
    activations_checkpoint_granularity: Optional[str] = "selective"

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def family(self) -> Family:
        return FAMILY

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None) -> "LlamaConfig":
        """Build from the reference-schema ``model:`` + ``distributed_strategy:``
        config blocks (plus optional HF-config-style keys)."""
        m = dict(model_cfg or {})
        ds = dict(ds_cfg or {})
        fusions = dict(m.get("fusions", {}) or {})
        if fusions.get("ulysses_attention"):
            # all-to-all CP attention — NOT in the reference's fusion set
            # (SURVEY.md §2.11: no Ulysses); a TPU-native extension
            impl = "ulysses"
        elif fusions.get("zigzag_ring_attention"):
            # balanced causal ring over the zig-zag layout — also an extension
            impl = "zigzag_ring"
        elif fusions.get("ring_attention"):
            impl = "ring"
        elif fusions.get("flash_attention"):
            impl = "flash"
        else:
            impl = "core"
        return cls(
            vocab_size=int(m.get("vocab_size", 32000)),
            hidden_size=int(m.get("hidden_size", 4096)),
            intermediate_size=int(m.get("intermediate_size", m.get("ffn_hidden_size", 11008))),
            num_layers=int(m.get("num_layers", m.get("num_hidden_layers", 32))),
            num_attention_heads=int(m.get("num_attention_heads", 32)),
            num_kv_heads=(
                int(m["num_key_value_heads"]) if m.get("num_key_value_heads") is not None else None
            ),
            max_position_embeddings=int(m.get("max_position_embeddings", 8192)),
            rope_theta=float(m.get("rope_theta", 10000.0)),
            rope_interpolation_factor=m.get("position_interpolation_factor"),
            rms_norm_eps=float(m.get("rms_norm_eps", 1e-5)),
            tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
            sliding_window=m.get("sliding_window"),
            fuse_qkv=bool(m.get("fuse_qkv", True)),
            attention_impl=impl,
            flash_block_q=fusions.get("flash_block_q"),
            flash_block_kv=fusions.get("flash_block_kv"),
            vocab_chunks=(int(fusions["chunked_ce"])
                          if fusions.get("chunked_ce") else None),
            sequence_parallel=bool(ds.get("sequence_parallel", False)),
            context_parallel=int(ds.get("context_parallel_size", 1)) > 1,
            activations_checkpoint_granularity=m.get(
                "activations_checkpoint_granularity", "selective"
            ),
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: LlamaConfig, dtype):
    """One decoder layer's params (unstacked). Returns (params, specs)."""
    keys = jax.random.split(key, 6)
    h, d = cfg.hidden_size, cfg.head_size
    nh, nkv = cfg.num_attention_heads, cfg.kv_heads
    params: dict[str, Any] = {}
    specs: dict[str, Any] = {}

    params["input_norm"], specs["input_norm"] = norm_ops.init_rms_norm(h, dtype=dtype)
    params["post_attn_norm"], specs["post_attn_norm"] = norm_ops.init_rms_norm(h, dtype=dtype)
    if cfg.post_sublayer_norms:
        for name in ("input_norm_2", "post_attn_norm_2"):
            params[name], specs[name] = norm_ops.init_rms_norm(h, dtype=dtype)

    std = cfg.initializer_range
    attn_p: dict[str, Any] = {}
    attn_s: dict[str, Any] = {}
    if cfg.fuse_qkv:
        # fused qkv ColumnParallel (reference modeling_llama.py:296-308)
        attn_p["qkv"], attn_s["qkv"] = linear_ops.init_linear(
            keys[0], h, (nh + 2 * nkv) * d, shard="column", dtype=dtype, stddev=std
        )
    else:
        attn_p["q"], attn_s["q"] = linear_ops.init_linear(
            keys[0], h, nh * d, shard="column", dtype=dtype, stddev=std
        )
        attn_p["k"], attn_s["k"] = linear_ops.init_linear(
            keys[1], h, nkv * d, shard="column", dtype=dtype, stddev=std
        )
        attn_p["v"], attn_s["v"] = linear_ops.init_linear(
            keys[2], h, nkv * d, shard="column", dtype=dtype, stddev=std
        )
    attn_p["o"], attn_s["o"] = linear_ops.init_linear(
        keys[3], nh * d, h, shard="row", dtype=dtype, stddev=std
    )
    params["attn"], specs["attn"] = attn_p, attn_s

    # fused gate_up ColumnParallel(stride=2) + RowParallel down
    # (reference modeling_llama.py:164-223)
    mlp_p: dict[str, Any] = {}
    mlp_s: dict[str, Any] = {}
    mlp_p["gate_up"], mlp_s["gate_up"] = linear_ops.init_linear(
        keys[4], h, 2 * cfg.intermediate_size, shard="column", dtype=dtype, stddev=std
    )
    mlp_p["down"], mlp_s["down"] = linear_ops.init_linear(
        keys[5], cfg.intermediate_size, h, shard="row", dtype=dtype, stddev=std
    )
    params["mlp"], specs["mlp"] = mlp_p, mlp_s
    return params, specs


def init_params(key: jax.Array, cfg: LlamaConfig, policy: DtypePolicy | None = None):
    """Init the full parameter pytree (layers stacked on a leading dim)."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    kemb, klayers, khead = jax.random.split(key, 3)

    params: dict[str, Any] = {}
    params["embed"], _ = linear_ops.init_embedding(
        kemb, cfg.vocab_size, cfg.hidden_size, dtype=dtype, stddev=cfg.initializer_range
    )
    layer_keys = jax.random.split(klayers, cfg.num_layers)
    params["layers"] = jax.vmap(lambda k: _init_layer(k, cfg, dtype)[0])(layer_keys)
    params["final_norm"], _ = norm_ops.init_rms_norm(cfg.hidden_size, dtype=dtype)
    if not cfg.tie_word_embeddings:
        # no-gather ColumnParallel lm_head (reference modeling_llama.py:808)
        params["lm_head"], _ = linear_ops.init_linear(
            khead, cfg.hidden_size, cfg.vocab_size, shard="column", dtype=dtype,
            stddev=cfg.initializer_range,
        )
    return params


def _layer_specs(cfg: LlamaConfig):
    """PartitionSpec tree matching one (unstacked) ``_init_layer`` output."""
    attn_s: dict[str, Any] = (
        {"qkv": {"w": P(None, "model")}}
        if cfg.fuse_qkv
        else {
            "q": {"w": P(None, "model")},
            "k": {"w": P(None, "model")},
            "v": {"w": P(None, "model")},
        }
    )
    attn_s["o"] = {"w": P("model", None)}
    norms = ("input_norm", "post_attn_norm") + (
        ("input_norm_2", "post_attn_norm_2") if cfg.post_sublayer_norms else ())
    return {
        **{name: {"scale": P(None)} for name in norms},
        "attn": attn_s,
        "mlp": {"gate_up": {"w": P(None, "model")}, "down": {"w": P("model", None)}},
    }


def param_specs(cfg: LlamaConfig, *, pipeline: bool = False):
    """PartitionSpec pytree matching ``init_params`` output.

    ``pipeline=True`` shards the stacked-layer dim over ``pipe`` — that single
    spec change IS the pipeline partitioning (equal cuts at layer granularity,
    the reference's ``auto_partition``, ``base.py:136-157``)."""
    stacked = jax.tree_util.tree_map(
        lambda s: P(*(("pipe" if pipeline else None,) + tuple(s))), _layer_specs(cfg),
        is_leaf=lambda x: isinstance(x, P),
    )
    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": stacked,
        "final_norm": {"scale": P(None)},
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


#: ``_attention_block``'s ``sliding_window``: the config's, for every layer
_CONFIG_WINDOW = object()


def _attention_block(lp, x, cos, sin, cfg: LlamaConfig, policy: DtypePolicy,
                     attention_mask=None, segment_ids=None, return_kv=False, *,
                     num_heads: Optional[int] = None, sliding_window=_CONFIG_WINDOW,
                     block_kv: Optional[int] = None):
    """``x`` (already normed) through qkv, rope, the attention op and ``o``.

    A stack whose layers differ (models/laguna.py) says per call what the
    config says once: ``num_heads`` query heads, the layer's
    ``sliding_window`` (``None``: none), its own ``cos`` / ``sin`` (which may
    rotate part of a head: ``ops.rope.apply_rope``) and key tile.  A leaf
    ``lp["gate"]`` (``[hidden, heads]``) is a per-head sigmoid gate read from
    ``x`` and applied to the op's output before ``o``.  Leaves
    ``lp["q_norm"]`` / ``lp["k_norm"]`` (``[head_dim]``, models/lfm2.py) are
    an RMS norm of every query / key head before the rope, one learned scale
    for all the heads.  ``cos`` None: q and k go to the op unrotated."""
    b, s, h = x.shape
    nh, nkv, d = num_heads or cfg.num_attention_heads, cfg.kv_heads, cfg.head_size
    if sliding_window is _CONFIG_WINDOW:
        sliding_window = cfg.sliding_window
    if cfg.fuse_qkv:
        qkv = linear_ops.apply_linear(lp["qkv"], x)
        q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    else:
        q = linear_ops.apply_linear(lp["q"], x)
        k = linear_ops.apply_linear(lp["k"], x)
        v = linear_ops.apply_linear(lp["v"], x)
    q = q.reshape(b, s, nh, d)
    k = k.reshape(b, s, nkv, d)
    v = v.reshape(b, s, nkv, d)
    q = shd.constrain(q, shd.heads_spec(cfg.context_parallel))
    if "q_norm" in lp:
        with jax.named_scope("qk_norm"):
            q = norm_ops.apply_rms_norm(lp["q_norm"], q, eps=cfg.rms_norm_eps)
            k = norm_ops.apply_rms_norm(lp["k_norm"], k, eps=cfg.rms_norm_eps)
    if cos is not None:   # None: no position embedding (models/nemotron_h.py)
        q = rope_ops.apply_rope(q, cos, sin)
        k = rope_ops.apply_rope(k, cos, sin)
    out = attn_ops.attention(
        q, k, v,
        impl=cfg.attention_impl,
        causal=True,
        sliding_window=sliding_window,
        softmax_dtype=policy.softmax_dtype,
        attention_mask=attention_mask,
        segment_ids=segment_ids,
        block_q=cfg.flash_block_q,
        block_kv=block_kv or cfg.flash_block_kv,
        keep_flash_outputs=_keeps_flash_outputs(cfg),
    )
    if "gate" in lp:
        with jax.named_scope("head_gate"):
            gate = jax.nn.sigmoid(
                linear_ops.apply_linear(lp["gate"], x).astype(policy.softmax_dtype))
            out = out * gate.astype(out.dtype)[..., None]
    out = out.reshape(b, s, nh * d)
    # RowParallel o_proj; reduce(-scatter under SP) inserted by GSPMD
    # (reference modeling_llama.py:475)
    out = linear_ops.apply_linear(lp["o"], out)
    if return_kv:
        return out, (k, v)  # rotated keys — the KV-cache contract
    return out


def _mlp_block(lp, x):
    gate_up = linear_ops.apply_linear(lp["gate_up"], x)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return linear_ops.apply_linear(lp["down"], jax.nn.silu(gate) * up)


def _decoder_layer(layer_params, x, cos, sin, cfg: LlamaConfig, policy: DtypePolicy,
                   attention_mask=None, segment_ids=None, return_kv=False):
    aspec = shd.act_spec(cfg.sequence_parallel, cfg.context_parallel)
    # scope names: telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("attention"):
        residual = x
        hidden = norm_ops.apply_rms_norm(layer_params["input_norm"], x, eps=cfg.rms_norm_eps)
        hidden = _attention_block(layer_params["attn"], hidden, cos, sin, cfg, policy,
                                  attention_mask=attention_mask,
                                  segment_ids=segment_ids, return_kv=return_kv)
        kv = None
        if return_kv:
            hidden, kv = hidden
        if cfg.post_sublayer_norms:
            hidden = norm_ops.apply_rms_norm(
                layer_params["input_norm_2"], hidden, eps=cfg.rms_norm_eps)
        x = shd.constrain(residual + hidden, aspec)
    with jax.named_scope("mlp"):
        residual = x
        hidden = norm_ops.apply_rms_norm(layer_params["post_attn_norm"], x, eps=cfg.rms_norm_eps)
        hidden = _mlp_block(layer_params["mlp"], hidden)
        if cfg.post_sublayer_norms:
            hidden = norm_ops.apply_rms_norm(
                layer_params["post_attn_norm_2"], hidden, eps=cfg.rms_norm_eps)
        x = shd.constrain(residual + hidden, aspec)
    if return_kv:
        return x, kv
    return x


#: ``selective`` recomputes the O(s^2) attention internals only — the
#: reference's activations_checkpoint_recompute: [CoreAttention]
_SELECTIVE_RECOMPUTES = ("attn_scores", "attn_probs")


def _remat_policy(granularity: Optional[str], kept: tuple = ()):
    if granularity == "full":
        # the layer's input and the flash forward kernel's two outputs: the
        # rerun rebuilds q, k and v from the input, not the kernel's o and lse
        # (16 ms of the MXU for 128 MiB at the latent-attention cell's shape);
        # ``kept``: what else a family's own forward rules name
        from neuronx_distributed_training_tpu.ops.flash_attention import KEPT_NAMES

        return jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES, *kept)
    if granularity == "selective":
        return jax.checkpoint_policies.save_anything_except_these_names(
            *_SELECTIVE_RECOMPUTES)
    return None


def _keeps_flash_outputs(cfg) -> bool:
    """``attn_ops.attention``'s ``keep_flash_outputs`` for a layer of ``cfg``'s
    stacks: whether ``_remat_policy`` keeps the names the kernel's forward
    rule would give its outputs."""
    return cfg.activations_checkpoint_granularity == "full"


def checkpoint_layer(body, cfg, *, stack: str, prevent_cse: bool = False,
                     kept: tuple = ()):
    """``body`` (a scanned stack's layer) rematerialized as
    ``cfg.activations_checkpoint_granularity`` says (``cfg``: a ``LlamaConfig``
    or what has its two fields read here), and what that keeps recorded among
    the trace's facts: ``remat`` of ``run_summary.json``, one entry a
    ``stack``.  ``kept``: the names a family's own forward rules give what
    ``full`` is to keep beside the flash kernel's outputs (none but for
    ``models/keye.py``).  ``flash_fwd_per_layer_application``: 1 where the forward
    kernel's outputs are kept or nothing is rematerialized, 2 where the rerun
    calls it again (``full`` around a context-parallel body, whose calls are
    not named); absent where the attention op is not a flash kernel."""
    granularity = cfg.activations_checkpoint_granularity
    policy = _remat_policy(granularity, kept)
    facts = shd.trace_facts()
    if facts is not None:
        if granularity == "full":
            from neuronx_distributed_training_tpu.ops.flash_attention import KEPT_NAMES

            entry = {"granularity": granularity, "kept": [*KEPT_NAMES, *kept]}
        else:
            entry = {"granularity": granularity, "kept": "all"}
            if granularity == "selective":
                entry["recomputed"] = list(_SELECTIVE_RECOMPUTES)
        if cfg.attention_impl != "core":
            entry["flash_fwd_per_layer_application"] = (
                2 if granularity == "full" and cfg.attention_impl != "flash" else 1)
        facts.setdefault("remat", {})[stack] = entry
    if policy is None:
        return body
    return jax.checkpoint(body, policy=policy, prevent_cse=prevent_cse)


def embed_and_rope(
    params,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    policy: DtypePolicy,
    *,
    positions: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
):
    """Embedded tokens ``[batch, seq, hidden]`` and the RoPE ``(cos, sin)``
    of their positions: what the decoder stack is applied to."""
    aspec = shd.act_spec(cfg.sequence_parallel, cfg.context_parallel)
    x = linear_ops.apply_embedding(params["embed"], input_ids, compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, aspec)

    if positions is None:
        # HF position_ids convention for padded batches (see positions_for);
        # packed chunks (segment_ids) reset RoPE phases per record
        positions = positions_for(input_ids, attention_mask, segment_ids)
    cos, sin = _rope_for(input_ids, cfg, positions)
    return x, cos, sin


def decoder_stack(layer_stack, x: jax.Array, cos, sin, cfg: LlamaConfig,
                  policy: DtypePolicy, *, attention_mask=None,
                  segment_ids=None) -> jax.Array:
    """One application of the scanned (and rematerialized) decoder stack to
    ``x``.  A stack applied several times (models/ouro.py) calls this once a
    pass with the same ``layer_stack``."""

    def body(carry, lp):
        # cast INSIDE the scan body (and remat boundary): only one layer's
        # bf16 copy is ever live, instead of a whole-stack bf16 duplicate —
        # ~2 bytes/param of HBM back under mixed precision
        lp = policy.cast_to_compute(lp)
        return _decoder_layer(lp, carry, cos, sin, cfg, policy,
                              attention_mask=attention_mask,
                              segment_ids=segment_ids), None

    x, _ = jax.lax.scan(checkpoint_layer(body, cfg, stack="layers"), x, layer_stack)
    return x


def hidden_states(
    params,
    input_ids: jax.Array,  # [batch, seq] (seq may be the per-CP-shard slice)
    cfg: LlamaConfig,
    policy: DtypePolicy,
    *,
    positions: Optional[jax.Array] = None,
    layers: Optional[Any] = None,  # override stacked layer params (pipeline stages)
    attention_mask: Optional[jax.Array] = None,  # [b, s] 1 = real token
    segment_ids: Optional[jax.Array] = None,  # [b, s] packed-record segments
) -> jax.Array:
    """Embedding + scanned decoder stack + final norm -> [batch, seq, hidden]."""
    x, cos, sin = embed_and_rope(params, input_ids, cfg, policy, positions=positions,
                                 attention_mask=attention_mask, segment_ids=segment_ids)
    x = decoder_stack(params["layers"] if layers is None else layers, x, cos, sin,
                      cfg, policy, attention_mask=attention_mask,
                      segment_ids=segment_ids)
    with jax.named_scope("ce_head"):
        return norm_ops.apply_rms_norm(params["final_norm"], x, eps=cfg.rms_norm_eps)


def logits_fn(params, hidden: jax.Array, cfg: LlamaConfig, policy: DtypePolicy) -> jax.Array:
    if cfg.tie_word_embeddings:
        w = params["embed"]["embedding"].astype(policy.compute_dtype)
        logits = hidden @ w.T
    else:
        logits = linear_ops.apply_linear(
            params["lm_head"], hidden, compute_dtype=policy.compute_dtype
        )
    return shd.constrain(logits, shd.logits_spec(cfg.context_parallel))


# ---------------------------------------------------------------------------
# pipeline-parallel hooks (parallel/pipeline.py contract)
# ---------------------------------------------------------------------------


def positions_for(input_ids: jax.Array, attention_mask=None,
                  segment_ids=None) -> jax.Array:
    """RoPE/absolute position ids [b, s]: plain arange, or — for padded
    batches — the HF convention of counting real tokens only
    (``cumsum(attention_mask) - 1``), keeping left-padded rows phase-aligned.
    ``segment_ids`` (packed chunks) reset positions at each record start so
    every packed record sees the RoPE phases it would see unpacked."""
    if segment_ids is not None:
        s = input_ids.shape[1]
        idx = jnp.arange(s, dtype=jnp.int32)[None, :]
        start = jnp.where(
            jnp.concatenate(
                [jnp.ones_like(segment_ids[:, :1], dtype=bool),
                 segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1),
            idx, 0,
        )
        # segments are contiguous runs: running max of start indices
        start = jax.lax.associative_scan(jnp.maximum, start, axis=1)
        return idx - start
    if attention_mask is not None:
        m = attention_mask.astype(jnp.int32)
        return jnp.clip(jnp.cumsum(m, axis=1) - 1, 0, None)
    positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
    return jnp.broadcast_to(positions, input_ids.shape)


def _rope_for(input_ids: jax.Array, cfg: LlamaConfig, positions=None):
    if positions is None:
        positions = positions_for(input_ids)
    inv_freq = rope_ops.rope_frequencies(
        cfg.head_size,
        theta=cfg.rope_theta,
        position_interpolation_factor=cfg.rope_interpolation_factor,
    )
    return rope_ops.rope_cos_sin(positions, inv_freq, dtype=jnp.float32)


def pipeline_hooks(cfg: LlamaConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    """(embed_fn, stage_fn, loss_fn) for ``parallel.pipeline.pipeline_loss``.

    The decoder stack is the pipelined region; embedding and lm-head/loss run
    outside it (replicated over ``pipe``, still TP-sharded), replacing the
    reference's stage-0/stage-N module placement + ``run_train`` engine
    (``base.py:374-383``).
    """
    aspec = shd.act_spec(cfg.sequence_parallel, cfg.context_parallel)

    def embed_fn(params, mb):
        x = linear_ops.apply_embedding(
            params["embed"], mb["input_ids"], compute_dtype=policy.compute_dtype,
        )
        return shd.constrain(x, aspec)

    def stage_fn(local_layers, x, mb):
        cos, sin = _rope_for(mb["input_ids"], cfg)

        def body(carry, lp):
            # per-layer cast inside the scan: one layer's bf16 copy live at
            # a time (see forward())
            lp = policy.cast_to_compute(lp)
            return _decoder_layer(lp, carry, cos, sin, cfg, policy), None

        x, _ = jax.lax.scan(body, x, local_layers)
        return x

    def loss_fn(params, y, mb):
        h = norm_ops.apply_rms_norm(params["final_norm"], y, eps=cfg.rms_norm_eps)
        labels = mb["labels"]
        loss_mask = mb.get("loss_mask")
        head_plain = cfg.tie_word_embeddings or (
            "lm_head" in params and "lora_a" not in params["lm_head"]
        )
        if cfg.vocab_chunks and head_plain:
            # fused head+CE per microbatch: the [mb, s, vocab] logits never
            # materialize — this is where the 405B-class config needs it
            if shift_labels:
                h2, labels2 = h[:, :-1], labels[:, 1:]
                lm2 = None if loss_mask is None else loss_mask[:, 1:]
            else:
                h2, labels2, lm2 = h, labels, loss_mask
            head_w = (params["embed"]["embedding"].T
                      if cfg.tie_word_embeddings else params["lm_head"]["w"])
            loss_sum = ce_ops.chunked_cross_entropy_from_hidden(
                h2, head_w, labels2, num_chunks=cfg.vocab_chunks,
                loss_mask=lm2, reduction="sum",
            )
            valid = (labels2 != -100).astype(jnp.float32)
            if lm2 is not None:
                valid = valid * lm2.astype(jnp.float32)
            return loss_sum, jnp.sum(valid)
        logits = logits_fn(params, h, cfg, policy)
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


def onef1b_head_hooks(cfg: LlamaConfig, policy: DtypePolicy):
    """Head wiring for ``parallel.pipeline.pipeline_loss_and_grad`` (1F1B).

    Returns ``(head_hidden_fn, head_params_of, head_weight_of, fold_grads)``:
    the hidden hook (final RMS norm), extractors for the head-param subtree
    and the [V, H] head matrix (tied embed or transposed ``lm_head.w`` —
    matching ``logits_fn``), and the folder that merges the 1F1B grad entries
    ``head_params``/``head_weight`` back into a params-shaped grad tree.
    Shared by the mixtral family (same top-level param layout, ``cfg.llama``).
    """
    tied = cfg.tie_word_embeddings

    def head_hidden_fn(hp, y):
        return norm_ops.apply_rms_norm(hp["final_norm"], y, eps=cfg.rms_norm_eps)

    def head_params_of(params):
        return {"final_norm": params["final_norm"]}

    def head_weight_of(params):
        w = (params["embed"]["embedding"] if tied else params["lm_head"]["w"].T)
        return w.astype(policy.compute_dtype)

    def fold_grads(grads, d_head_params, d_head_weight):
        grads = dict(grads)
        grads["final_norm"] = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(a.dtype),
            grads["final_norm"], d_head_params["final_norm"],
        )
        if tied:
            emb = grads["embed"]["embedding"]
            grads["embed"] = {
                **grads["embed"],
                "embedding": emb + d_head_weight.astype(emb.dtype),
            }
        else:
            w = grads["lm_head"]["w"]
            grads["lm_head"] = {
                **grads["lm_head"],
                "w": w + d_head_weight.T.astype(w.dtype),
            }
        return grads

    return head_hidden_fn, head_params_of, head_weight_of, fold_grads


def forward(
    params,
    batch: dict[str, jax.Array],
    cfg: LlamaConfig,
    policy: DtypePolicy,
    *,
    positions: Optional[jax.Array] = None,
    shift_labels: bool = True,
    return_logits: bool = False,
):
    """Full causal-LM forward -> (loss, aux).

    ``batch`` keys follow the reference's HF input_names contract:
    ``input_ids``, optional ``labels``, optional ``loss_mask``
    (``llama_model.py:94-101``).  Under CP, callers pre-shift labels on host and
    pass ``shift_labels=False`` (reference ``modeling_llama.py:815-823``).
    """
    input_ids = batch["input_ids"]
    attention_mask = batch.get("attention_mask")
    segment_ids = batch.get("segment_ids")
    hidden = hidden_states(params, input_ids, cfg, policy, positions=positions,
                           attention_mask=attention_mask,
                           segment_ids=segment_ids)
    with jax.named_scope("ce_head"):
        return _head_loss(params, hidden, batch, cfg, policy,
                          shift_labels=shift_labels, return_logits=return_logits)


def _head_loss(params, hidden, batch, cfg: LlamaConfig, policy: DtypePolicy, *,
               shift_labels: bool, return_logits: bool):
    """``forward``'s head: logits (or the fused chunked head) and the loss."""
    attention_mask = batch.get("attention_mask")
    labels = batch.get("labels")
    head_plain = cfg.tie_word_embeddings or (
        "lm_head" in params and "lora_a" not in params["lm_head"]
    )
    if (cfg.vocab_chunks and labels is not None and not return_logits
            and head_plain):  # an lm_head LoRA adapter needs apply_linear
        # fused head+CE: the [b, s, vocab] logits are never materialized
        # (see ce_ops.chunked_cross_entropy_from_hidden)
        loss_mask = batch.get("loss_mask")
        if attention_mask is not None:
            am = attention_mask.astype(jnp.float32)
            loss_mask = am if loss_mask is None else loss_mask * am
        if shift_labels:
            hidden = hidden[:, :-1]
            labels = labels[:, 1:]
            loss_mask = None if loss_mask is None else loss_mask[:, 1:]
        if cfg.tie_word_embeddings:
            head_w = params["embed"]["embedding"].T
        else:
            head_w = params["lm_head"]["w"]
        loss = ce_ops.chunked_cross_entropy_from_hidden(
            hidden, head_w, labels,
            num_chunks=cfg.vocab_chunks, loss_mask=loss_mask,
        )
        return loss, {}
    logits = logits_fn(params, hidden, cfg, policy)
    aux: dict[str, Any] = {}
    if return_logits:
        aux["logits"] = logits
    if labels is None:
        return logits, aux
    loss_mask = batch.get("loss_mask")
    if attention_mask is not None:
        # padded positions never contribute to the loss
        am = attention_mask.astype(jnp.float32)
        loss_mask = am if loss_mask is None else loss_mask * am
    if shift_labels:
        logits, labels, loss_mask = ce_ops.shift_for_next_token(logits, labels, loss_mask)
    loss = ce_ops.cross_entropy_loss(logits, labels, loss_mask=loss_mask)
    return loss, aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def _loss(cfg: LlamaConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    if cfg.attention_impl != "zigzag_ring":
        return lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)
    # zig-zag CP layout: the loss permutes the batch (labels pre-shifted in
    # ORIGINAL order — the in-model shift is order-dependent) and feeds
    # matching RoPE positions; cp is the mesh's, as the op reads it, and
    # cp == 1 makes both transforms the identity
    from neuronx_distributed_training_tpu.parallel.ring_attention import (
        zigzag_positions,
        zigzag_transform_batch,
    )

    if not shift_labels:
        raise NotImplementedError(
            "zigzag_ring_attention with a pre-shifted data module "
            "(the zig-zag transform owns the label shift)"
        )

    def loss_fn(p, batch, key):
        mesh = shd.active_mesh()
        cp = int(mesh.shape.get("context", 1)) if mesh is not None else 1
        zb = zigzag_transform_batch(batch, cp)
        s = zb["input_ids"].shape[1]
        pos = jnp.broadcast_to(zigzag_positions(s, cp)[None, :], zb["input_ids"].shape)
        return forward(p, zb, cfg, policy, positions=pos, shift_labels=False)

    return loss_fn


def _logits(cfg: LlamaConfig, policy: DtypePolicy):
    if cfg.attention_impl == "zigzag_ring":
        # preference batches are chosen/rejected sequences, not the
        # zig-zag-permuted LM batches the layout expects
        raise NotImplementedError(
            "zigzag_ring_attention with preference alignment; use "
            "fusions.ring_attention"
        )

    def fwd(p, b, rng=None):
        logits, _ = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, 0.0

    return fwd


def head(cfg: LlamaConfig, policy: DtypePolicy, *, norm: bool = True):
    """``Family.head``; the families built on this block pass ``cfg.llama``."""

    def head_fn(p, hidden):
        if norm:
            hidden = norm_ops.apply_rms_norm(p["final_norm"], hidden, eps=cfg.rms_norm_eps)
        return logits_fn(p, hidden, cfg, policy)

    return head_fn


def _under_pp(hooks):
    """``hooks(cfg, policy, ...)`` for the ``pipe`` axis: refuses the zig-zag
    layout, whose batch/position transform lives in the non-PP loss (stage
    hooks thread no positions)."""

    def build(cfg: LlamaConfig, policy: DtypePolicy, **kw):
        if cfg.attention_impl == "zigzag_ring":
            raise NotImplementedError(
                "zigzag_ring_attention under pipeline parallelism; use "
                "fusions.ring_attention for pp + cp configs"
            )
        return hooks(cfg, policy, **kw)

    return build


def _decode():
    from neuronx_distributed_training_tpu.models import decode

    return decode.prefill, decode.decode_step


def flops_breakdown(cfg: LlamaConfig, seq_len: int, passes: int = 1) -> dict[str, float]:
    """``Family.flops_breakdown``; a stack applied several times
    (models/ouro.py) multiplies its work, heads included."""
    attn = passes * cfg.num_layers * _attention_flops_per_token(
        hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_attention_heads,
        num_kv_heads=cfg.num_kv_heads,
        seq_len=seq_len,
        head_dim=cfg.head_dim,
    )
    mlp = 2 * cfg.hidden_size * 3 * cfg.intermediate_size
    return {
        "attention": attn,
        "mlp": float(passes * cfg.num_layers * mlp),
        "router": 0.0,
        "head": 2.0 * passes * cfg.hidden_size * cfg.vocab_size,
    }


def plan_shape(cfg: LlamaConfig) -> dict[str, Any]:
    """``Family.plan_shape``: a dense stack's."""
    return {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_attention_heads,
        "num_kv_heads": cfg.kv_heads, "head_dim": cfg.head_size,
        "hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
        "vocab": cfg.vocab_size, "tied_embeddings": cfg.tie_word_embeddings,
    }


FAMILY = Family(
    name="llama",
    config_from=LlamaConfig.from_config,
    loss=_loss,
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    plan_shape=plan_shape,
    logits=_logits,
    head=head,
    pipeline=_under_pp(lambda cfg, policy, **kw: (pipeline_hooks(cfg, policy, **kw), {})),
    onef1b_head=_under_pp(onef1b_head_hooks),
    decode=_decode,
)
