"""Sharded linear / embedding primitives.

TPU-native counterparts of NxD's ``ColumnParallelLinear`` / ``RowParallelLinear`` /
``ParallelEmbedding`` (used throughout the reference, e.g. ``modeling_llama.py:
74-78, 185-203, 296-357``).  There is no wrapper class and no hand-written
collective: a "column-parallel" linear is a plain matmul whose weight carries a
``P(None, "model")`` spec; a "row-parallel" linear's weight carries
``P("model", None)`` and GSPMD inserts the reduce(-scatter).  Fused variants
(``fuse_qkv``, fused ``gate_up_proj`` — reference ``modeling_llama.py:164-223,
296-348``) are just wider column-parallel weights.

Each ``init_*`` returns ``(params, specs)`` — a param pytree and a matching
PartitionSpec pytree.  Weights are stored ``[in, out]`` (column-major for the
MXU-friendly ``x @ w`` contraction).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _normal_init(key, shape, dtype, stddev: float):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def init_linear(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    shard: str,  # "column" | "row" | "replicated"
    dtype=jnp.float32,
    stddev: float = 0.02,
    use_bias: bool = False,
):
    """Init a linear layer's params and specs.

    ``shard="column"`` shards the output dim over ``model`` (NxD
    ColumnParallelLinear); ``"row"`` shards the input dim (RowParallelLinear);
    ``"replicated"`` shards nothing.
    """
    wkey, _ = jax.random.split(key)
    params = {"w": _normal_init(wkey, (in_dim, out_dim), dtype, stddev)}
    if shard == "column":
        wspec = P(None, "model")
        bspec = P("model")
    elif shard == "row":
        wspec = P("model", None)
        bspec = P(None)
    elif shard == "replicated":
        wspec = P(None, None)
        bspec = P(None)
    else:
        raise ValueError(f"unknown shard mode {shard!r}")
    specs = {"w": wspec}
    if use_bias:
        params["bias"] = jnp.zeros((out_dim,), dtype)
        specs["bias"] = bspec
    return params, specs


def apply_linear(params, x: jax.Array, *, compute_dtype=None) -> jax.Array:
    w = params["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "lora_a" in params:
        # low-rank adapter path (peft/lora.py): y += x @ A @ B * (alpha/r).
        # scaling is stored in the (tiny, fp32) "lora_scale" leaf so apply
        # stays a pure function of params.
        a = params["lora_a"].astype(y.dtype)
        b = params["lora_b"].astype(y.dtype)
        y = y + ((x @ a) @ b) * params["lora_scale"].astype(y.dtype)
    if "bias" in params:
        b = params["bias"]
        y = y + (b.astype(y.dtype) if compute_dtype is not None else b)
    return y


def init_embedding(
    key: jax.Array,
    vocab_size: int,
    hidden: int,
    *,
    dtype=jnp.float32,
    stddev: float = 0.02,
):
    """Vocab-sharded embedding table (NxD ``ParallelEmbedding``,
    reference ``modeling_llama.py:550,634``): ``[vocab, hidden]`` with vocab over
    ``model``.  The lookup is a gather; GSPMD resolves out-of-shard rows with the
    same masked-sum trick NxD implements by hand."""
    params = {"embedding": _normal_init(key, (vocab_size, hidden), dtype, stddev)}
    specs = {"embedding": P("model", None)}
    return params, specs


def apply_embedding(params, ids: jax.Array, *, compute_dtype=None,
                    via_matmul: bool = False) -> jax.Array:
    """Embedding lookup.

    ``via_matmul`` computes ``one_hot(ids) @ table`` instead of a gather: the
    backward pass is then a ``dot_general`` rather than a scatter-add.  XLA's
    SPMD partitioner CHECK-crashes partitioning the gather-transpose scatter
    when its consumer is DP-resharded (ZeRO-1 moments) inside the manual
    ``pipe`` submesh (spmd_partitioner_util.cc:495) — the pipeline used this
    form until the embed hook moved OUTSIDE the manual region
    (``parallel/pipeline.py``), where the cheap gather partitions fine; the
    option remains for any future in-manual-region embedding.  With a
    TP-sharded table the contraction form is also exactly Megatron's
    vocab-parallel embedding (mask-local-vocab + all-reduce), done by GSPMD.
    """
    table = params["embedding"]
    # scope names: telemetry.spans.DEVICE_SCOPES (the backward of the gather,
    # a scatter-add into the table's gradient, lands under it too)
    with jax.named_scope("embed"):
        if via_matmul:
            dtype = compute_dtype or table.dtype
            oh = jax.nn.one_hot(ids, table.shape[0], dtype=dtype)
            return oh @ table.astype(dtype)
        out = jnp.take(table, ids, axis=0)
        if compute_dtype is not None:
            out = out.astype(compute_dtype)
        return out


def pad_vocab_size(vocab_size: int, make_divisible_by: int, tp: int) -> int:
    """Pad vocab so it divides evenly across TP shards — the reference's
    ``make_vocab_size_divisible_by * tp`` padding (``data/base.py:66-89``)."""
    multiple = make_divisible_by * tp
    return ((vocab_size + multiple - 1) // multiple) * multiple
