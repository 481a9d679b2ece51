"""Operations and bytes a gated-short-convolution / attention stack with
sigmoid-routed experts (``architecture: lfm2``) needs, from shapes alone.
Conventions are ``benchmark/flops.py``'s: a matmul of ``[m, k] x [k, n]`` is
``2 m k n``; backward = 2 x forward, so a trained token needs 3 x its forward
operations; recomputed operations are not required operations; a causal query
sees ``i + 1`` keys; embedding lookups, norms (the per-head ones too), RoPE,
softmax, the router's sigmoid and top-k, the selection bias's rule and the
optimizer are not counted.

What the family changes: a convolution layer's operator is two projections
(``hidden -> 3 x hidden`` and ``hidden -> hidden``), ``conv_L_cache`` taps a
channel (a multiply and an add each) and two gates (a multiply a channel
each); an attention layer's kernels score and weigh over heads of ``hidden /
heads`` dims (64 at the published sizes).  Of the routed experts only the
slots THIS chip holds count: a token fills ``num_experts_per_tok`` slots over
all experts, of which ``held / num_experts`` fall here when routing is even
(the selection bias steers it there).  The rows really received move with the
data (``moe/held_rows_share``); the count does not.  The head is tied to the
embedding: one matmul of ``hidden x vocabulary`` a token, as an untied one.
"""

from __future__ import annotations

import itertools
from typing import Any, Mapping

from benchmark import flops


def sizes(model: Mapping[str, Any]) -> dict:
    n = int(model["num_hidden_layers"])
    experts = int(model.get("num_experts", 0) or 0)
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    types = list(model.get("layer_types") or ["conv"] * n)[:n]
    return {
        "h": int(model["hidden_size"]), "f": int(model["intermediate_size"]),
        "H": heads, "G": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "K": int(model.get("conv_L_cache", 3)),
        "L": n, "conv": types.count("conv"), "attn": types.count("full_attention"),
        "types": types,
        "dense": min(int(model.get("num_dense_layers", 2)), n) if experts else n,
        "V": int(model["vocab_size"]),
        "E": experts, "k": int(model.get("num_experts_per_tok", 0) or 0),
        "held": int(held[1]) - int(held[0]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
    }


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token: ``total`` = 6 x
    the matmul parameters a token meets (the convolution layers' two
    projections, the attention layers' qkv and o, dense MLPs, router, the held
    experts' expected slots, the tied head) + 3 x the convolution's taps and
    gates + 3 x the score and context matmuls at the mean visible keys."""
    c = sizes(model)
    h, H, G, d = c["h"], c["H"], c["G"], c["d"]
    slots = c["k"] * c["held"] / c["E"] if c["E"] else 0.0
    keys = flops.mean_visible_keys(seq_len, None)
    sparse = c["L"] - c["dense"]
    out = {
        "conv_projections": c["conv"] * 6.0 * (h * 3 * h + h * h),
        # K multiply-adds and two gate multiplies a channel
        "conv_gate": c["conv"] * 3.0 * (2 * c["K"] * h + 2 * h),
        "qkv_and_o": c["attn"] * 6.0 * (h * (H + 2 * G) * d + H * d * h),
        "scores": c["attn"] * 3.0 * 2 * H * 2 * d * keys,
        "dense_mlp": c["dense"] * 6.0 * 3 * h * c["f"],
        "router": sparse * 6.0 * h * c["E"],
        "held_experts": sparse * 6.0 * slots * 3 * h * c["fe"],
        "head": 6.0 * h * c["V"],
    }
    return {"total": sum(out.values()), **out, "held_slots_per_token": slots}


def flash_call(model: Mapping[str, Any], seq_len: int, rows: int,
               itemsize: int = 2) -> dict:
    """Operations and bytes of one call of each flash kernel on ``rows``
    causal sequences, heads of ``d`` dims.  Per visible (query, key) pair and
    query head: the forward scores and weighs (``4 d``); dq recomputes the
    scores, then ``dO V^T`` and ``dS K`` (``6 d``); dkv recomputes the scores,
    then ``P^T dO``, ``dO V^T`` and ``dS^T Q`` (``8 d``).  Bytes are the
    operands as they are fed (64 wide, nothing padded), each read once and
    each result written once: q, o and their cotangents for the ``H`` query
    heads, k, v and theirs for the ``G`` key/value heads."""
    c = sizes(model)
    s, H, G, d = int(seq_len), c["H"], c["G"], c["d"]
    pairs = rows * H * s * flops.mean_visible_keys(s, None)
    q = rows * H * s * d * itemsize          # q, o, do, dq
    kv = rows * G * s * d * itemsize         # k, v, dk, dv
    row = rows * H * s * 4                   # lse / delta, float32
    return {
        "fwd": {"flops": 2 * pairs * 2 * d, "bytes": q + 2 * kv + q + row},
        "dq": {"flops": 2 * pairs * 3 * d, "bytes": q + 2 * kv + q + 2 * row + q},
        "dkv": {"flops": 2 * pairs * 4 * d, "bytes": q + 2 * kv + q + 2 * row + 2 * kv},
    }


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel: ``flops`` and ``bytes`` of one call and the ``calls``
    a traced step runs of it on one chip: micro-batches x attention layers for
    each of the three.  The forward kernel runs ONCE a layer application:
    under ``full`` recomputation the layer keeps the kernel's ``o`` and
    ``lse`` and its rerun does not call it (PR 40), and a run of one layer is
    merged with its rerun; the convolution layers call none."""
    c = sizes(model)
    micro = int(traffic["micro_batches"])
    rows = int(traffic["global_batch_size"]) // micro // int(data_parallel)
    return {kind: {**need, "calls": micro * c["attn"]} for kind, need in flash_call(
        model, int(traffic["seq_length"]), rows).items()}


def conv_gate_call(model: Mapping[str, Any], tokens: int, itemsize: int = 2) -> dict:
    """Operations and bytes of one call of each kernel of the convolution's
    middle (``ops/short_conv.py``) on ``tokens`` tokens of one layer.  Forward:
    the projection's three thirds read, the result written (``4 x hidden`` a
    token), a multiply-add a tap and two gate multiplies a channel.  Backward:
    the three thirds and the result's cotangent read, the thirds' cotangents
    written (``7 x hidden``; the taps' gradient is ``[K, hidden]`` float32 a
    tile: nothing beside them), the forward's products again and about three
    times them for the cotangents.  Both are memory-bound by two orders."""
    c = sizes(model)
    h, k = c["h"], c["K"]
    return {
        "fwd": {"flops": tokens * (2 * k + 2) * h, "bytes": tokens * 4 * h * itemsize},
        "bwd": {"flops": tokens * 4 * (2 * k + 2) * h, "bytes": tokens * 7 * h * itemsize},
    }


def conv_gate_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                    data_parallel: int) -> dict:
    """Per kernel of the convolution's middle: ``flops`` and ``bytes`` of one
    call and the ``calls`` a traced step runs of it on one chip.  Backward:
    micro-batches x convolution layers.  Forward: once a layer application,
    and once more where the layer is rematerialized (its out-projection's
    gradient needs the middle's result again): under recomputation every
    convolution layer whose run of equal layers is longer than one (a run of
    one is an unrolled scan whose rerun the compiler merges with its first
    run: PERF.md section 7, PR 38)."""
    c = sizes(model)
    micro = int(traffic["micro_batches"])
    rows = int(traffic["global_batch_size"]) // micro // int(data_parallel)
    remat = bool(model.get("activations_checkpoint_granularity"))
    kinds = [(t, i < c["dense"]) for i, t in enumerate(c["types"])]
    forward = 0
    for (operator, _), run in itertools.groupby(kinds):     # runs of equal kinds, in order
        n = len(list(run))
        if operator == "conv":
            forward += n * (2 if remat and n > 1 else 1)
    calls = {"fwd": micro * forward, "bwd": micro * c["conv"]}
    return {kind: {**need, "calls": calls[kind]} for kind, need in conv_gate_call(
        model, rows * int(traffic["seq_length"])).items()}
