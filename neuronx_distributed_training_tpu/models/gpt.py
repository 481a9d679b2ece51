"""Megatron-family GPT model, TPU-native.

Functional re-design of the reference's Megatron model source
(``models/megatron/gpt_model.py`` + ``language_model.py`` + ``transformer.py``,
~3500 LoC of NeMo-Megatron-on-NxD): the architecture-knob surface of
``megatron_gpt_model.py:79-147`` reduced to the knobs that change math —

- position embedding: ``rope`` | ``learned_absolute``
  (``language_model.py:194-328`` Embedding + RotaryEmbedding);
- normalization: ``layernorm`` (with bias) | ``rmsnorm``
  (``fused_layer_norm.py:14-36``);
- activation: ``gelu`` | ``swiglu`` | ``geglu`` | ``reglu``
  (``transformer.py:89-245`` ParallelMLP variants);
- biased linears (Megatron default) vs bias-free;
- GQA / MQA via ``num_query_groups`` (``transformer.py:470-777``);
- optional sliding-window attention; dropout (embedding/hidden) with explicit
  PRNG threading;
- MoE layers (``NeuronSwitchMLP``, ``transformer.py:376-467``) via
  ``ops.moe`` with top-k or sinkhorn routing;
- transformer block layouts ``pre_ln`` (default) | ``post_ln`` | ``normformer``
  | ``gpt_j`` (``transformer.py:1468-2084``) and optional tokentype
  embeddings (``language_model.py:194-328``).

Normformer deviation: the reference computes the mid-MLP LayerNorm
per-TP-partition (width ``ffn/tp``, no cross-shard stats); here it is a true
LayerNorm over the full ffn width — GSPMD inserts the reduction, and the
numerics don't change with tp.

Loss is the same vocab-parallel CE as Llama.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models.family import Family, Refused
from neuronx_distributed_training_tpu.ops import cross_entropy as ce_ops
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy
from neuronx_distributed_training_tpu.utils.perf import _attention_flops_per_token


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """The ``megatron`` ``model:`` block (reference ``megatron_gpt_model.py:79-147``)."""

    vocab_size: int = 50257
    hidden_size: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4*h (or 8/3*h for glu acts)
    num_layers: int = 12
    num_attention_heads: int = 16
    num_query_groups: Optional[int] = None  # GQA; 1 = MQA; None = MHA
    max_position_embeddings: int = 2048
    position_embedding_type: str = "rope"  # "rope" | "learned_absolute"
    rotary_percentage: float = 1.0
    rope_theta: float = 10000.0
    normalization: str = "layernorm"  # "layernorm" | "rmsnorm"
    layernorm_epsilon: float = 1e-5
    activation: str = "gelu"  # "gelu" | "swiglu" | "geglu" | "reglu"
    bias: bool = True
    hidden_dropout: float = 0.0
    embedding_dropout: float = 0.0
    sliding_window: Optional[int] = None
    # block layout: "pre_ln" | "post_ln" | "normformer" | "gpt_j"
    # (reference transformer.py:1468-2084)
    transformer_block_type: str = "pre_ln"
    # tokentype (segment) embeddings; 0 = none (language_model.py:194-328)
    num_tokentypes: int = 0
    share_embeddings_and_output_weights: bool = True  # Megatron default tying
    initializer_range: float = 0.02
    attention_impl: str = "core"
    flash_block_q: Optional[int] = None   # Pallas tile knobs, fusions.flash_block_*
    flash_block_kv: Optional[int] = None  # (also the blockwise/ring kv block)
    sequence_parallel: bool = False
    activations_checkpoint_granularity: Optional[str] = "selective"
    # MoE (NeuronSwitchMLP equivalent); None -> dense
    moe: Optional[moe_ops.MoEConfig] = None
    moe_frequency: int = 1  # MoE every Nth layer (reference megatron_gpt_model.py:137)

    @property
    def kv_heads(self) -> int:
        return self.num_query_groups or self.num_attention_heads

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ffn_size(self) -> int:
        if self.ffn_hidden_size:
            return self.ffn_hidden_size
        return 4 * self.hidden_size

    @property
    def is_glu(self) -> bool:
        return self.activation in ("swiglu", "geglu", "reglu")

    @property
    def family(self) -> Family:
        return FAMILY

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        m = dict(model_cfg or {})
        ds = dict(ds_cfg or {})
        fusions = dict(m.get("fusions", {}) or {})
        moe_block = m.get("moe") or (
            {"num_experts": m["num_moe_experts"]} if m.get("num_moe_experts") else None
        )
        moe_freq = int((moe_block or {}).get("frequency", 1) or 1)
        return cls(
            vocab_size=int(m.get("vocab_size", 50257)),
            hidden_size=int(m.get("hidden_size", 1024)),
            ffn_hidden_size=m.get("ffn_hidden_size"),
            num_layers=int(m.get("num_layers", 12)),
            num_attention_heads=int(m.get("num_attention_heads", 16)),
            num_query_groups=m.get("num_query_groups", m.get("num_kv_heads")),
            max_position_embeddings=int(m.get("max_position_embeddings", 2048)),
            position_embedding_type=str(m.get("position_embedding_type", "rope")),
            rotary_percentage=float(m.get("rotary_percentage", 1.0)),
            rope_theta=float(m.get("rotary_base", m.get("rope_theta", 10000.0))),
            normalization=str(m.get("normalization", "layernorm")),
            layernorm_epsilon=float(m.get("layernorm_epsilon", 1e-5)),
            activation=str(m.get("activation", "gelu")),
            bias=bool(m.get("has_bias", m.get("bias", True))),
            hidden_dropout=float(m.get("hidden_dropout", 0.0)),
            embedding_dropout=float(m.get("embedding_dropout", m.get("hidden_dropout", 0.0))),
            sliding_window=m.get(
                "sliding_window_size", m.get("window_size", m.get("sliding_window"))
            ),
            transformer_block_type=str(m.get("transformer_block_type", "pre_ln")),
            num_tokentypes=int(m.get("num_tokentypes", 0) or 0),
            share_embeddings_and_output_weights=bool(
                m.get("share_embeddings_and_output_weights", True)
            ),
            attention_impl="flash" if fusions.get("flash_attention") else "core",
            flash_block_q=fusions.get("flash_block_q"),
            flash_block_kv=fusions.get("flash_block_kv"),
            sequence_parallel=bool(ds.get("sequence_parallel", False)),
            activations_checkpoint_granularity=m.get(
                "activations_checkpoint_granularity", "selective"
            ),
            moe=moe_ops.MoEConfig.from_config(moe_block) if moe_block else None,
            moe_frequency=moe_freq,
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


BLOCK_TYPES = ("pre_ln", "post_ln", "normformer", "gpt_j")


def _norm_init(cfg: GPTConfig, dtype, width: Optional[int] = None):
    width = width or cfg.hidden_size
    if cfg.normalization == "rmsnorm":
        return norm_ops.init_rms_norm(width, dtype=dtype)[0]
    return norm_ops.init_layer_norm(width, dtype=dtype)[0]


def _apply_norm(cfg: GPTConfig, params, x):
    if cfg.normalization == "rmsnorm":
        return norm_ops.apply_rms_norm(params, x, eps=cfg.layernorm_epsilon)
    return norm_ops.apply_layer_norm(params, x, eps=cfg.layernorm_epsilon)


def _init_layer(key: jax.Array, cfg: GPTConfig, dtype, *, moe_layer=None):
    """``moe_layer`` overrides the MLP kind (None -> cfg.moe decides)."""
    keys = jax.random.split(key, 6)
    h, d = cfg.hidden_size, cfg.head_size
    nh, nkv = cfg.num_attention_heads, cfg.kv_heads
    std = cfg.initializer_range
    bias = cfg.bias
    if cfg.transformer_block_type not in BLOCK_TYPES:
        raise ValueError(
            f"unknown transformer_block_type {cfg.transformer_block_type!r}; "
            f"supported: {BLOCK_TYPES}"
        )
    if cfg.transformer_block_type == "normformer" and cfg.moe is not None:
        raise ValueError(
            "normformer blocks are dense-only (the mid-MLP norm has no "
            "expert equivalent); use pre_ln or post_ln with MoE"
        )
    p: dict[str, Any] = {
        "input_norm": _norm_init(cfg, dtype),
        # every layout keeps both norms — gpt_j's parallel residual norms the
        # attn branch with input_norm and the MLP branch with post_attn_norm
        # (two independent parameter sets, reference transformer.py:1908-1914)
        "post_attn_norm": _norm_init(cfg, dtype),
    }
    if cfg.transformer_block_type == "normformer":
        # extra norms: after the attention output (h) and after the MLP
        # activation (ffn width) — reference transformer.py normformer layout
        p["nf_attn_norm"] = _norm_init(cfg, dtype)
        p["nf_mlp_norm"] = _norm_init(cfg, dtype, width=cfg.ffn_size)
    p["attn"] = {
        "qkv": linear_ops.init_linear(
            keys[0], h, (nh + 2 * nkv) * d, shard="column", dtype=dtype,
            stddev=std, use_bias=bias,
        )[0],
        "o": linear_ops.init_linear(
            keys[1], nh * d, h, shard="row", dtype=dtype, stddev=std, use_bias=bias
        )[0],
    }
    is_moe = (cfg.moe is not None) if moe_layer is None else moe_layer
    if is_moe:
        p["mlp"] = moe_ops.init_moe_params(
            keys[2], h, cfg.ffn_size, cfg.moe, dtype=dtype, stddev=std
        )
    else:
        width = 2 * cfg.ffn_size if cfg.is_glu else cfg.ffn_size
        p["mlp"] = {
            "up": linear_ops.init_linear(
                keys[2], h, width, shard="column", dtype=dtype, stddev=std,
                use_bias=bias,
            )[0],
            "down": linear_ops.init_linear(
                keys[3], cfg.ffn_size, h, shard="row", dtype=dtype, stddev=std,
                use_bias=bias,
            )[0],
        }
    return p


def num_moe_layers(cfg: GPTConfig) -> int:
    """Layer ``i`` is MoE iff ``i % moe_frequency == 0`` (reference
    ``megatron_gpt_model.py:137`` + mixtral's interleave rule)."""
    f = cfg.moe_frequency
    if cfg.num_layers % f != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} must divide by moe frequency {f}"
        )
    return cfg.num_layers // f


def init_params(key: jax.Array, cfg: GPTConfig, policy: DtypePolicy | None = None):
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    kemb, kpos, klayers, khead = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    params["embed"], _ = linear_ops.init_embedding(
        kemb, cfg.vocab_size, cfg.hidden_size, dtype=dtype, stddev=cfg.initializer_range
    )
    if cfg.position_embedding_type == "learned_absolute":
        params["pos_embed"] = {
            "embedding": (
                cfg.initializer_range
                * jax.random.truncated_normal(
                    kpos, -2.0, 2.0, (cfg.max_position_embeddings, cfg.hidden_size)
                )
            ).astype(dtype)
        }
    if cfg.num_tokentypes > 0:
        # segment embeddings (reference language_model.py:194-328)
        params["tokentype_embed"] = {
            "embedding": (
                cfg.initializer_range
                * jax.random.truncated_normal(
                    jax.random.fold_in(kpos, 7), -2.0, 2.0,
                    (cfg.num_tokentypes, cfg.hidden_size),
                )
            ).astype(dtype)
        }
    layer_keys = jax.random.split(klayers, cfg.num_layers)
    if cfg.moe is not None and cfg.moe_frequency > 1:
        f, g = cfg.moe_frequency, num_moe_layers(cfg)
        dense_stack = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, moe_layer=False)
        )(layer_keys)
        moe_keys = jax.random.split(jax.random.fold_in(klayers, 999), g)
        moe_mlp = jax.vmap(
            lambda k: moe_ops.init_moe_params(
                k, cfg.hidden_size, cfg.ffn_size, cfg.moe,
                dtype=dtype, stddev=cfg.initializer_range,
            )
        )(moe_keys)
        dense_mlp = jax.tree_util.tree_map(
            lambda x: x.reshape((g, f) + x.shape[1:])[:, 1:],
            dense_stack["mlp"],
        )
        dense_stack["mlp"] = {"moe": moe_mlp, "dense": dense_mlp}
        params["layers"] = dense_stack
    else:
        params["layers"] = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    if cfg.transformer_block_type != "post_ln":
        # post_ln layers end with their own LN — the reference builds no
        # final_layernorm for that layout (transformer.py:2478, 2569-2570)
        params["final_norm"] = _norm_init(cfg, dtype)
    if not cfg.share_embeddings_and_output_weights:
        params["lm_head"], _ = linear_ops.init_linear(
            khead, cfg.hidden_size, cfg.vocab_size, shard="column", dtype=dtype,
            stddev=cfg.initializer_range,
        )
    return params


def _norm_specs(cfg: GPTConfig):
    if cfg.normalization == "rmsnorm":
        return {"scale": P(None)}
    return {"scale": P(None), "bias": P(None)}


def param_specs(cfg: GPTConfig, *, pipeline: bool = False):
    n = _norm_specs(cfg)
    attn: dict[str, Any] = {
        "qkv": {"w": P(None, "model")},
        "o": {"w": P("model", None)},
    }
    if cfg.bias:
        attn["qkv"]["bias"] = P("model")
        attn["o"]["bias"] = P(None)
    dense_mlp = {"up": {"w": P(None, "model")}, "down": {"w": P("model", None)}}
    if cfg.bias:
        dense_mlp["up"]["bias"] = P("model")
        dense_mlp["down"]["bias"] = P(None)
    if cfg.moe is not None and cfg.moe_frequency > 1:
        mlp = None  # grouped; filled below after stacking
    elif cfg.moe is not None:
        mlp = moe_ops.moe_param_specs(cfg.moe)
    else:
        mlp = dense_mlp
    layer = {"input_norm": n, "post_attn_norm": n, "attn": attn,
             "mlp": mlp if mlp is not None else dense_mlp}
    if cfg.transformer_block_type == "normformer":
        layer["nf_attn_norm"] = n
        layer["nf_mlp_norm"] = n
    lead = "pipe" if pipeline else None
    stacked = jax.tree_util.tree_map(
        lambda s: P(*((lead,) + tuple(s))), layer, is_leaf=lambda x: isinstance(x, P)
    )
    if cfg.moe is not None and cfg.moe_frequency > 1:
        # grouped layout: moe leads [G] and dense [G, f-1]; under pipeline
        # both lead with "pipe" (pp slices whole MoE+dense groups, matching
        # the flat [L] attn/norm slices since L/pp == (G/pp)*f)
        moe_specs = jax.tree_util.tree_map(
            lambda s: P(*((lead,) + tuple(s))), moe_ops.moe_param_specs(cfg.moe),
            is_leaf=lambda x: isinstance(x, P),
        )
        grouped_dense = jax.tree_util.tree_map(
            lambda s: P(*((tuple(s)[0], None) + tuple(s)[1:])), stacked["mlp"],
            is_leaf=lambda x: isinstance(x, P),
        )
        stacked["mlp"] = {"moe": moe_specs, "dense": grouped_dense}
    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": stacked,
    }
    if cfg.transformer_block_type != "post_ln":
        specs["final_norm"] = _norm_specs(cfg)
    if cfg.position_embedding_type == "learned_absolute":
        specs["pos_embed"] = {"embedding": P(None, None)}
    if cfg.num_tokentypes > 0:
        specs["tokentype_embed"] = {"embedding": P(None, None)}
    if not cfg.share_embeddings_and_output_weights:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _activation(cfg: GPTConfig, x: jax.Array) -> jax.Array:
    if cfg.is_glu:
        a, b = jnp.split(x, 2, axis=-1)
        gate = {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu,
                "reglu": jax.nn.relu}[cfg.activation](a)
        return gate * b
    return jax.nn.gelu(x)


def _dropout(x, rate, key):
    if rate <= 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _attention_block(cfg, lp, x, cos, sin, policy, attention_mask=None,
                     segment_ids=None, return_kv=False):
    b, s, h = x.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_size
    qkv = linear_ops.apply_linear(lp["qkv"], x)
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    q = q.reshape(b, s, nh, d)
    k = k.reshape(b, s, nkv, d)
    v = v.reshape(b, s, nkv, d)
    q = shd.constrain(q, shd.heads_spec(False))
    if cos is not None:
        if cfg.rotary_percentage < 1.0:
            rot = int(d * cfg.rotary_percentage) // 2 * 2
            q = jnp.concatenate(
                [rope_ops.apply_rope(q[..., :rot], cos, sin), q[..., rot:]], -1
            )
            k = jnp.concatenate(
                [rope_ops.apply_rope(k[..., :rot], cos, sin), k[..., rot:]], -1
            )
        else:
            q = rope_ops.apply_rope(q, cos, sin)
            k = rope_ops.apply_rope(k, cos, sin)
    out = attn_ops.attention(
        q, k, v, impl=cfg.attention_impl, causal=True,
        sliding_window=cfg.sliding_window, softmax_dtype=policy.softmax_dtype,
        attention_mask=attention_mask, segment_ids=segment_ids,
        block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
        keep_flash_outputs=cfg.activations_checkpoint_granularity == "full",
    )
    out = linear_ops.apply_linear(lp["o"], out.reshape(b, s, nh * d))
    if return_kv:
        return out, (k, v)
    return out


def _mlp_block(cfg, lp, x, policy, mid_norm=None):
    if cfg.moe is not None and "router" in lp:
        # gpt has no context parallelism: moe_block's default act_spec holds
        y, aux = moe_ops.moe_block(lp, x, cfg.moe, compute_dtype=policy.compute_dtype,
                                   reduce_dtype=policy.reduce_dtype)
        aux_loss = moe_ops.weighted_router_loss(
            aux["router_logits"], aux["expert_idx"], cfg.moe
        )
        return y, aux_loss
    y = linear_ops.apply_linear(lp["up"], x)
    y = _activation(cfg, y)
    if mid_norm is not None:
        # normformer mid-MLP norm (full ffn width; see module docstring for
        # the per-partition deviation from the reference)
        y = _apply_norm(cfg, mid_norm, y)
    return linear_ops.apply_linear(lp["down"], y), jnp.zeros((), jnp.float32)


def _decoder_layer(cfg, lp, x, cos, sin, policy, dropout_key,
                   attention_mask=None, segment_ids=None, return_kv=False):
    """One transformer block in the configured layout
    (reference ``transformer.py:1468-2084``):

    - ``pre_ln``      x += drop(attn(LN1(x)));        x += drop(mlp(LN2(x)))
    - ``post_ln``     x = LN1(x + drop(attn(x)));     x = LN2(x + drop(mlp(x)))
    - ``normformer``  x += drop(LNa(attn(LN1(x))));   x += drop(mlp_mid(LN2(x)))
    - ``gpt_j``       x += drop(attn(LN1(x))) + drop(mlp(LN2(x)))
      (parallel residual; LN1/LN2 are two independent norms, reference
      ``transformer.py:1908-1914``)
    """
    aspec = shd.act_spec(cfg.sequence_parallel, False)
    bt = cfg.transformer_block_type
    k1 = k2 = None
    if dropout_key is not None:
        k1, k2 = jax.random.split(dropout_key)

    if bt == "gpt_j":
        attn_in = _apply_norm(cfg, lp["input_norm"], x)
        attn_out = _attention_block(cfg, lp["attn"], attn_in, cos, sin, policy,
                                    attention_mask=attention_mask,
                                    segment_ids=segment_ids,
                                    return_kv=return_kv)
        kv = None
        if return_kv:
            attn_out, kv = attn_out
        mlp_in = _apply_norm(cfg, lp["post_attn_norm"], x)
        mlp_out, aux_loss = _mlp_block(cfg, lp["mlp"], mlp_in, policy)
        x = shd.constrain(
            x + _dropout(attn_out, cfg.hidden_dropout, k1)
            + _dropout(mlp_out, cfg.hidden_dropout, k2), aspec)
        if return_kv:
            return x, aux_loss, kv
        return x, aux_loss

    residual = x
    attn_in = x if bt == "post_ln" else _apply_norm(cfg, lp["input_norm"], x)
    hidden = _attention_block(cfg, lp["attn"], attn_in, cos, sin, policy,
                              attention_mask=attention_mask,
                              segment_ids=segment_ids,
                              return_kv=return_kv)
    kv = None
    if return_kv:
        hidden, kv = hidden
    if bt == "normformer":
        hidden = _apply_norm(cfg, lp["nf_attn_norm"], hidden)
    x = residual + _dropout(hidden, cfg.hidden_dropout, k1)
    if bt == "post_ln":
        x = _apply_norm(cfg, lp["input_norm"], x)
    x = shd.constrain(x, aspec)

    residual = x
    mlp_in = x if bt == "post_ln" else _apply_norm(cfg, lp["post_attn_norm"], x)
    hidden, aux_loss = _mlp_block(
        cfg, lp["mlp"], mlp_in, policy,
        mid_norm=lp.get("nf_mlp_norm") if bt == "normformer" else None,
    )
    x = residual + _dropout(hidden, cfg.hidden_dropout, k2)
    if bt == "post_ln":
        x = _apply_norm(cfg, lp["post_attn_norm"], x)
    x = shd.constrain(x, aspec)
    if return_kv:
        return x, aux_loss, kv
    return x, aux_loss


def _add_tokentype(cfg: GPTConfig, params, x, tokentype_ids):
    """Add segment embeddings (reference ``language_model.py:194-328``):
    ids present without a table is a config error; a table without ids adds
    nothing (the reference's optional-tokentype contract)."""
    if tokentype_ids is None:
        return x
    if cfg.num_tokentypes <= 0:
        raise ValueError(
            "batch has tokentype_ids but model.num_tokentypes is 0; set "
            "num_tokentypes to the number of segment types"
        )
    return x + jnp.take(
        params["tokentype_embed"]["embedding"], tokentype_ids, axis=0
    ).astype(x.dtype)


def _rope_for(cfg: GPTConfig, input_ids: jax.Array, positions=None):
    if cfg.position_embedding_type == "learned_absolute":
        return None, None
    if positions is None:
        from neuronx_distributed_training_tpu.models.llama import positions_for

        positions = positions_for(input_ids)
    rot_dim = int(cfg.head_size * cfg.rotary_percentage) // 2 * 2
    inv_freq = rope_ops.rope_frequencies(rot_dim, theta=cfg.rope_theta)
    return rope_ops.rope_cos_sin(positions, inv_freq, dtype=jnp.float32)


def _group_xs(cfg: GPTConfig, layer_stack):
    """Grouped scan inputs (see ``ops.moe.group_interleaved_stack``)."""
    return moe_ops.group_interleaved_stack(cfg.moe_frequency, layer_stack)


def _grouped_scan(cfg: GPTConfig, layer_stack, cos, sin, policy,
                  layer_keys=None, attention_mask=None, segment_ids=None):
    """(xs, body) for the dense/MoE interleave scan over [G] groups.

    Shared by ``forward`` and the pipeline ``stage_fn`` (mirrors
    ``mixtral._grouped_scan``; the body differs by GPT's dropout-key
    threading).  Each group runs one MoE layer then ``f-1`` dense layers;
    groups are contiguous runs of ``f`` layers, so any contiguous slice of
    the flat attn/norm stack aligns with the matching moe/dense group slices
    — which is what makes the layout pipeline-sliceable.  Dropout keys group
    as ``[g, f]`` so every layer keeps a unique key.
    """
    f = cfg.moe_frequency
    g = jax.tree_util.tree_leaves(layer_stack["mlp"]["moe"])[0].shape[0]
    grouped = _group_xs(cfg, layer_stack)
    moe_xs, dense_xs = grouped["moe"], grouped["dense"]
    gkeys = (
        layer_keys.reshape((g, f) + layer_keys.shape[1:])
        if layer_keys is not None else None
    )

    def body(carry, inp):
        x, aux_acc = carry
        if gkeys is not None:
            mxs, dxs, keys_g = inp
            k0 = keys_g[0]
        else:
            mxs, dxs = inp
            k0 = None
        # per-group cast inside the scan (one group's bf16 copy live at a time)
        mxs = policy.cast_to_compute(mxs)
        x, aux = _decoder_layer(cfg, mxs, x, cos, sin, policy, k0,
                                attention_mask=attention_mask,
                                segment_ids=segment_ids)

        def dense_body(carry2, dinp):
            x2, acc2 = carry2
            if gkeys is not None:
                dlp, dk = dinp
            else:
                dlp, dk = dinp, None
            dlp = policy.cast_to_compute(dlp)
            x2, a2 = _decoder_layer(cfg, dlp, x2, cos, sin, policy, dk,
                                    attention_mask=attention_mask,
                                    segment_ids=segment_ids)
            return (x2, acc2 + a2), None

        dxs_in = (dxs, keys_g[1:]) if gkeys is not None else dxs
        (x, aux_acc2), _ = jax.lax.scan(
            dense_body, (x, jnp.zeros((), jnp.float32)), dxs_in)
        return (x, aux_acc + aux + aux_acc2), None

    xs = ((moe_xs, dense_xs, gkeys) if gkeys is not None
          else (moe_xs, dense_xs))
    return xs, body


def _logits_from_hidden(params, hidden, cfg: GPTConfig, policy: DtypePolicy):
    if cfg.share_embeddings_and_output_weights:
        w = params["embed"]["embedding"].astype(policy.compute_dtype)
        logits = hidden @ w.T
    else:
        logits = linear_ops.apply_linear(
            params["lm_head"], hidden, compute_dtype=policy.compute_dtype
        )
    return shd.constrain(logits, shd.logits_spec(False))


def pipeline_hooks(cfg: GPTConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    """(embed_fn, stage_fn, loss_fn) for ``parallel.pipeline.pipeline_loss``.

    Dropout PRNG: the trainer threads per-microbatch keys via ``mb["_rng"]``
    (uint32 ``[2]`` leaves); each stage folds in its pipe rank and vp chunk
    (``mb["_chunk"]``) so every (layer, microbatch) pair gets a unique key —
    the reference's per-stage dropout seeding under NxDPPModel.  ``stage_fn``
    returns ``(x, aux)``; pass ``stage_aux=True`` (aux is the MoE router loss,
    0 for dense).
    """
    aspec = shd.act_spec(cfg.sequence_parallel, False)

    def embed_fn(params, mb):
        ids = mb["input_ids"]
        s = ids.shape[1]
        x = linear_ops.apply_embedding(
            params["embed"], ids, compute_dtype=policy.compute_dtype,
        )
        if cfg.position_embedding_type == "learned_absolute":
            x = x + jnp.take(
                params["pos_embed"]["embedding"], jnp.arange(s), axis=0
            ).astype(x.dtype)[None]
        x = _add_tokentype(cfg, params, x, mb.get("tokentype_ids"))
        rng = mb.get("_rng")
        if rng is not None and cfg.embedding_dropout > 0.0:
            x = _dropout(x, cfg.embedding_dropout, jax.random.fold_in(rng, 0x0E))
        return shd.constrain(x, aspec)

    def stage_fn(local_layers, x, mb):
        cos, sin = _rope_for(cfg, mb["input_ids"])
        grouped = cfg.moe is not None and cfg.moe_frequency > 1
        if grouped:
            # local layer count = local groups x f (flat attn/norm slices)
            n_local = (
                jax.tree_util.tree_leaves(local_layers["mlp"]["moe"])[0].shape[0]
                * cfg.moe_frequency
            )
        else:
            n_local = jax.tree_util.tree_leaves(local_layers)[0].shape[0]
        rng = mb.get("_rng")
        layer_keys = None
        if rng is not None and cfg.hidden_dropout > 0.0:
            try:
                rank = jax.lax.axis_index("pipe")
            except NameError:
                rank = 0  # pp == 1 fallback path (no manual pipe axis)
            stage_rng = jax.random.fold_in(
                jax.random.fold_in(rng, rank), mb.get("_chunk", 0)
            )
            layer_keys = jax.random.split(stage_rng, n_local)
        if grouped:
            # grouped interleave on the LOCAL slice (see _grouped_scan)
            xs, body = _grouped_scan(cfg, local_layers, cos, sin, policy,
                                     layer_keys=layer_keys)
        elif layer_keys is not None:

            def body(carry, inp):
                x, aux_acc = carry
                lp, lkey = inp
                lp = policy.cast_to_compute(lp)
                x, aux = _decoder_layer(cfg, lp, x, cos, sin, policy, lkey)
                return (x, aux_acc + aux), None

            xs = (local_layers, layer_keys)
        else:

            def body(carry, lp):
                x, aux_acc = carry
                lp = policy.cast_to_compute(lp)
                x, aux = _decoder_layer(cfg, lp, x, cos, sin, policy, None)
                return (x, aux_acc + aux), None

            xs = local_layers
        (x, aux_sum), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, aux_sum

    def loss_fn(params, y, mb):
        hidden = (y if cfg.transformer_block_type == "post_ln"
                  else _apply_norm(cfg, params["final_norm"], y))
        logits = _logits_from_hidden(params, hidden, cfg, policy)
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
    cfg: GPTConfig,
    policy: DtypePolicy,
    *,
    rng: Optional[jax.Array] = None,  # dropout PRNG; None = eval/deterministic
    shift_labels: bool = True,
    return_logits: bool = False,
):
    """Causal-LM forward -> (loss, aux) (or (logits, aux) without labels)."""
    from neuronx_distributed_training_tpu.models.llama import positions_for

    input_ids = batch["input_ids"]
    attention_mask = batch.get("attention_mask")
    segment_ids = batch.get("segment_ids")
    b, s = input_ids.shape
    aspec = shd.act_spec(cfg.sequence_parallel, False)
    positions = positions_for(input_ids, attention_mask, segment_ids)
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype
    )
    if cfg.position_embedding_type == "learned_absolute":
        x = x + jnp.take(
            params["pos_embed"]["embedding"], positions, axis=0
        ).astype(x.dtype)
    x = _add_tokentype(cfg, params, x, batch.get("tokentype_ids"))
    cos, sin = _rope_for(cfg, input_ids, positions=positions)
    if rng is not None:
        rng, kemb = jax.random.split(rng)
        x = _dropout(x, cfg.embedding_dropout, kemb)
    x = shd.constrain(x, aspec)

    layer_stack = params["layers"]
    layer_keys = (
        jax.random.split(rng, cfg.num_layers) if rng is not None else None
    )

    if cfg.moe is not None and cfg.moe_frequency > 1:
        # grouped interleave: scan over [L/f] groups of (MoE + f-1 dense)
        xs, body = _grouped_scan(cfg, layer_stack, cos, sin, policy,
                                 layer_keys=layer_keys,
                                 attention_mask=attention_mask,
                                 segment_ids=segment_ids)
    else:

        def body(carry, inp):
            x, aux_acc = carry
            if layer_keys is not None:
                lp, lkey = inp
            else:
                lp, lkey = inp, None
            lp = policy.cast_to_compute(lp)  # per-layer cast (see llama)
            x, aux = _decoder_layer(cfg, lp, x, cos, sin, policy, lkey,
                                    attention_mask=attention_mask,
                                    segment_ids=segment_ids)
            return (x, aux_acc + aux), None

        xs = (layer_stack, layer_keys) if layer_keys is not None else layer_stack

    from neuronx_distributed_training_tpu.models.llama import checkpoint_layer

    body = checkpoint_layer(body, cfg, stack="layers")
    (x, aux_sum), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    # post_ln layers already end normalized; the reference has no final LN
    # for that layout (transformer.py:2478, 2569-2570)
    hidden = (x if cfg.transformer_block_type == "post_ln"
              else _apply_norm(cfg, params["final_norm"], x))
    logits = _logits_from_hidden(params, hidden, cfg, policy)

    aux: dict[str, Any] = {}
    if cfg.moe is not None:
        # already coefficient-weighted (weighted_router_loss); averaged over
        # the layers that HAVE routers
        aux["router_aux_loss"] = aux_sum / num_moe_layers(cfg)
    if return_logits:
        aux["logits"] = logits
    labels = batch.get("labels")
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
    if cfg.moe is not None:
        loss = loss + aux["router_aux_loss"]
    return loss, aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def _logits(cfg: GPTConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):  # rng: dropout (None in the frozen reference pass)
        logits, aux = forward(p, {"input_ids": b["input_ids"]}, cfg, policy, rng=rng)
        return logits, aux.get("router_aux_loss", 0.0)

    return fwd


def _head(cfg: GPTConfig, policy: DtypePolicy, *, norm: bool = True):
    def head_fn(p, hidden):
        # post_ln layers end normalized; no final LN (init_params omits it)
        if norm and cfg.transformer_block_type != "post_ln":
            hidden = _apply_norm(cfg, p["final_norm"], hidden)
        return _logits_from_hidden(p, hidden, cfg, policy)

    return head_fn


def _pipeline(cfg: GPTConfig, policy: DtypePolicy, *, shift_labels: bool = True):
    return pipeline_hooks(cfg, policy, shift_labels=shift_labels), {
        "stage_aux": True,
        # normalized over the layers that HAVE routers (moe_frequency)
        "aux_inv_layers": 1.0 / num_moe_layers(cfg) if cfg.moe is not None else 0.0,
        "needs_rng": cfg.hidden_dropout > 0.0 or cfg.embedding_dropout > 0.0,
    }


def _decode():
    from neuronx_distributed_training_tpu.models import decode

    return decode.prefill_gpt, decode.decode_step_gpt


def _flops_breakdown(cfg: GPTConfig, seq_len: int) -> dict[str, float]:
    attn = cfg.num_layers * _attention_flops_per_token(
        hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_attention_heads,
        num_kv_heads=cfg.kv_heads,
        seq_len=seq_len,
        head_dim=cfg.head_size,
    )
    matmuls = 3 if cfg.is_glu else 2  # (gate,) up, down
    mlp = 2 * cfg.hidden_size * matmuls * cfg.ffn_size
    n_moe, top_k, experts = (
        (num_moe_layers(cfg), cfg.moe.top_k, cfg.moe.num_experts)
        if cfg.moe is not None else (0, 0, 0))
    return {
        "attention": attn,
        "mlp": float((cfg.num_layers - n_moe) * mlp + n_moe * top_k * mlp),
        "router": float(n_moe * 2 * cfg.hidden_size * experts),
        "head": 2.0 * cfg.hidden_size * cfg.vocab_size,
    }


FAMILY = Family(
    name="gpt",
    config_from=GPTConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, rng=key,
                                      shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=_flops_breakdown,
    plan_shape=lambda cfg: {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_attention_heads,
        "num_kv_heads": cfg.kv_heads, "head_dim": cfg.head_size,
        "hidden": cfg.hidden_size, "ffn": cfg.ffn_size, "vocab": cfg.vocab_size,
        "tied_embeddings": cfg.share_embeddings_and_output_weights,
        "num_experts": int(cfg.moe.num_experts) if cfg.moe is not None else 0,
        "top_k": int(cfg.moe.top_k) if cfg.moe is not None else 0,
        "moe_frequency": int(cfg.moe_frequency or 1)},
    logits=_logits,
    head=_head,
    pipeline=_pipeline,
    # learned positions, dropout threading and the post_ln/normformer/gpt_j
    # head variants keep the autodiff wavefront until a head is wired
    onef1b_head=Refused(
        "GPTConfig: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=_decode,
    moe_groups=lambda cfg: num_moe_layers(cfg) if cfg.moe_frequency != 1 else None,
)
