"""Operations and bytes a stack of single-mixer layers (``architecture:
nemotron_h``: Mamba-2 state-space mixers, sigmoid-routed non-gated experts
beside a shared expert, attention with no position embedding) needs, from
shapes alone.  Conventions are ``benchmark/flops.py``'s: a matmul of ``[m, k] x
[k, n]`` is ``2 m k n``; backward = 2 x forward, so a trained token needs 3 x
its forward operations; recomputed operations are not required operations; a
causal query sees ``i + 1`` keys; embedding lookups, norms (the gated one too),
softmax, softplus and the decays' exponentials, the router's sigmoid and
top-k, the selection bias's rule and the optimizer are not counted.

What the family changes: a Mamba-2 layer is two projections (``hidden ->
d_inner + (d_inner + 2 x groups x state) + heads`` and ``d_inner -> hidden``,
``d_inner = heads x head_dim``), ``conv_kernel`` taps a channel (a multiply
and an add each) and the scan, counted as the RECURRENCE's own work whatever
computes it: per head and token a multiply-add for each of the ``head_dim x
state`` entries of ``S`` for the decay, for ``dt x B^T`` and for ``S C``, and
one a channel for ``D x``.  A non-gated expert is two matrices, not three.  Of
the routed experts only the slots THIS chip holds count: a token fills
``num_experts_per_tok`` slots over all experts, of which ``held / experts``
fall here when routing is even (the selection bias steers it there); the
shared expert is whole.  The head is untied: one matmul of ``hidden x
vocabulary`` a token.
"""

from __future__ import annotations

from typing import Any, Mapping

from benchmark import flops


def sizes(model: Mapping[str, Any]) -> dict:
    n = int(model["num_hidden_layers"])
    pattern = str(model["hybrid_override_pattern"])[:n]
    experts = int(model.get("n_routed_experts", 0) or 0)
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    H, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N = int(model["n_groups"]), int(model["ssm_state_size"])
    return {
        "h": int(model["hidden_size"]), "L": n,
        "mamba": pattern.count("M"), "moe": pattern.count("E"), "attn": pattern.count("*"),
        "H": H, "P": P, "G": G, "N": N, "K": int(model.get("conv_kernel", 4)),
        "inner": H * P, "conv": H * P + 2 * G * N,
        "Hq": heads, "Gk": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "V": int(model["vocab_size"]),
        "E": experts, "k": int(model.get("num_experts_per_tok", 0) or 0),
        "held": int(held[1]) - int(held[0]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
        "fs": int(model.get("moe_shared_expert_intermediate_size", 0) or 0),
    }


def scan_flops_per_token(c: Mapping[str, Any]) -> float:
    """The recurrence's forward operations a token a layer."""
    return 6.0 * c["inner"] * c["N"] + 2.0 * c["inner"]


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token: ``total`` = 6 x
    the matmul parameters a token meets (the Mamba-2 layers' two projections,
    the attention layers' qkv and o, router, shared expert, the held experts'
    expected slots, the head) + 3 x the convolution's taps and the
    recurrence + 3 x the score and context matmuls at the mean visible keys."""
    c = sizes(model)
    h = c["h"]
    slots = c["k"] * c["held"] / c["E"] if c["E"] else 0.0
    keys = flops.mean_visible_keys(seq_len, None)
    out = {
        "mamba_projections": c["mamba"] * 6.0 * (
            h * (c["inner"] + c["conv"] + c["H"]) + c["inner"] * h),
        "mamba_conv": c["mamba"] * 3.0 * 2 * c["K"] * c["conv"],
        "ssd_scan": c["mamba"] * 3.0 * scan_flops_per_token(c),
        "qkv_and_o": c["attn"] * 6.0 * (h * (c["Hq"] + 2 * c["Gk"]) * c["d"]
                                        + c["Hq"] * c["d"] * h),
        "scores": c["attn"] * 3.0 * 2 * c["Hq"] * 2 * c["d"] * keys,
        "router": c["moe"] * 6.0 * h * c["E"],
        "shared_expert": c["moe"] * 6.0 * 2 * h * c["fs"],
        "held_experts": c["moe"] * 6.0 * slots * 2 * h * c["fe"],
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
    operands as they are fed, each read once and each result written once: q,
    o and their cotangents for the ``Hq`` query heads, k, v and theirs for the
    ``Gk`` key/value heads (16 query heads read one)."""
    c = sizes(model)
    s, H, G, d = int(seq_len), c["Hq"], c["Gk"], c["d"]
    pairs = rows * H * s * flops.mean_visible_keys(s, None)
    q = rows * H * s * d * itemsize          # q, o, do, dq
    kv = rows * G * s * d * itemsize         # k, v, dk, dv
    row = rows * H * s * 4                   # lse / delta, float32
    return {
        "fwd": {"flops": 2 * pairs * 2 * d, "bytes": q + 2 * kv + q + row},
        "dq": {"flops": 2 * pairs * 3 * d, "bytes": q + 2 * kv + q + 2 * row + q},
        "dkv": {"flops": 2 * pairs * 4 * d, "bytes": q + 2 * kv + q + 2 * row + 2 * kv},
    }


def _rows(traffic: Mapping[str, Any], data_parallel: int) -> tuple:
    micro = int(traffic["micro_batches"])
    return micro, int(traffic["global_batch_size"]) // micro // int(data_parallel)


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel: ``flops`` and ``bytes`` of one call and the ``calls``
    a traced step runs of it on one chip: micro-batches x attention layers for
    each of the three.  The forward kernel runs ONCE a layer application:
    under ``full`` recomputation the layer keeps the kernel's ``o`` and
    ``lse`` and its rerun does not call it (PR 40); the Mamba-2 and sparse
    layers call none."""
    c = sizes(model)
    micro, rows = _rows(traffic, data_parallel)
    return {kind: {**need, "calls": micro * c["attn"]} for kind, need in flash_call(
        model, int(traffic["seq_length"]), rows).items()}


def ssd_call(model: Mapping[str, Any], tokens: int, itemsize: int = 2) -> dict:
    """The REQUIRED work of one Mamba-2 layer's scan on ``tokens`` tokens,
    whatever computes it.  Forward: the recurrence's operations; ``x`` read and
    ``y`` written (``heads x head_dim`` each), ``B`` and ``C`` read (``groups x
    state`` each), ``dt`` read (``heads``).  Backward: twice the operations;
    the forward's four operands and ``y``'s cotangent read, the four operands'
    cotangents written.  Both are memory-bound."""
    c = sizes(model)
    operands = c["inner"] + 2 * c["G"] * c["N"] + c["H"]      # x, B, C, dt a token
    ops = tokens * scan_flops_per_token(c)
    return {
        "fwd": {"flops": ops, "bytes": tokens * (operands + c["inner"]) * itemsize},
        "bwd": {"flops": 2 * ops, "bytes": tokens * (2 * operands + c["inner"]) * itemsize},
    }


def ssd_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
              data_parallel: int) -> dict:
    """Per pass of the scan: ``flops`` and ``bytes`` of one layer's and the
    ``calls`` a step REQUIRES of it on one chip: one forward and one backward
    a Mamba-2 layer a micro-batch.  A rerun under ``full`` recomputation, and
    the block's own rerun inside the backward pass (``ops/ssd.py``), are not
    required work: they count against the share."""
    c = sizes(model)
    micro, rows = _rows(traffic, data_parallel)
    return {kind: {**need, "calls": micro * c["mamba"]} for kind, need in ssd_call(
        model, rows * int(traffic["seq_length"])).items()}
