"""Operations and bytes latent attention with sigmoid-routed experts
(``architecture: kanana``) needs, from shapes alone.  Conventions are
``benchmark/flops.py``'s: a matmul of ``[m, k] x [k, n]`` is ``2 m k n``;
backward = 2 x forward, so a trained token needs 3 x its forward operations;
recomputed operations are not required operations; a causal query sees ``i +
1`` keys; embedding lookups, norms, RoPE, softmax, the router's sigmoid and
top-k, the selection bias's rule and the optimizer are not counted.

What latent attention changes: five projections a layer (q to ``H x d_qk``;
the down-projection to the latent and the shared rotated key; the
up-projection from the latent to per-head keys and values; o from ``H x
d_v``), and kernels that score over ``d_qk = qk_nope_head_dim +
qk_rope_head_dim`` dims and weigh values of ``d_v``.  Of the routed experts
only the slots THIS chip holds count: a token fills ``num_experts_per_tok``
slots over all experts, of which ``held / n_routed_experts`` fall here when
routing is even (the selection bias steers it there).  The rows really
received move with the data (``moe/held_rows_share``); the count does not.
"""

from __future__ import annotations

from typing import Any, Mapping

from benchmark import flops


def sizes(model: Mapping[str, Any]) -> dict:
    n = int(model["num_hidden_layers"])
    experts = int(model.get("n_routed_experts", 0) or 0)
    held = model.get("num_experts_held") or [0, experts]
    dense = min(int(model.get("first_k_dense_replace", 1)), n) if experts else n
    return {
        "h": int(model["hidden_size"]), "f": int(model["intermediate_size"]),
        "H": int(model["num_attention_heads"]),
        "dn": int(model["qk_nope_head_dim"]), "dr": int(model["qk_rope_head_dim"]),
        "dv": int(model["v_head_dim"]), "r": int(model["kv_lora_rank"]),
        "L": n, "dense": dense, "sparse": n - dense, "V": int(model["vocab_size"]),
        "E": experts, "k": int(model.get("num_experts_per_tok", 0) or 0),
        "held": int(held[1]) - int(held[0]),
        "fe": int(model.get("moe_intermediate_size", 0) or 0),
        "shared": int(model.get("n_shared_experts", 0) or 0),
    }


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token: ``total`` = 6 x
    the matmul parameters a token meets (q and o, the latent's down- and
    up-projection, dense MLP, router, shared experts, the held experts'
    expected slots, head) + 3 x the score and context matmuls at the mean
    visible keys, over ``d_qk`` and ``d_v``."""
    c = sizes(model)
    h, H, L = c["h"], c["H"], c["L"]
    d_qk = c["dn"] + c["dr"]
    slots = c["k"] * c["held"] / c["E"] if c["E"] else 0.0
    keys = flops.mean_visible_keys(seq_len, None)
    out = {
        "q_and_o": L * 6.0 * (h * H * d_qk + H * c["dv"] * h),
        "latent": L * 6.0 * (h * (c["r"] + c["dr"]) + c["r"] * H * (c["dn"] + c["dv"])),
        "scores": L * 3.0 * 2 * H * (d_qk + c["dv"]) * keys,
        "dense_mlp": c["dense"] * 6.0 * 3 * h * c["f"],
        "router": c["sparse"] * 6.0 * h * c["E"],
        "shared_experts": c["sparse"] * 6.0 * 3 * h * c["shared"] * c["fe"],
        "held_experts": c["sparse"] * 6.0 * slots * 3 * h * c["fe"],
        "head": 6.0 * h * c["V"],
    }
    return {"total": sum(out.values()), **out, "held_slots_per_token": slots}


def flash_call(model: Mapping[str, Any], seq_len: int, rows: int,
               itemsize: int = 2) -> dict:
    """Operations and bytes of one call of each flash kernel on ``rows``
    causal sequences.  Per visible (query, key) pair and head: the forward
    scores over ``d_qk`` and weighs ``d_v``; dq recomputes the scores, then
    ``dO V^T`` over ``d_v`` and ``dS K`` over ``d_qk``; dkv recomputes the
    scores, then ``P^T dO`` and ``dO V^T`` over ``d_v`` and ``dS^T Q`` over
    ``d_qk``.  Bytes are the operands as they are fed, each read once and each
    result written once: q and k ``d_qk`` wide for every head (the shared
    rotated key is repeated in k), v, o and their cotangents ``d_v`` wide."""
    c = sizes(model)
    s, H = int(seq_len), c["H"]
    d_qk, d_v = c["dn"] + c["dr"], c["dv"]
    pairs = rows * H * s * flops.mean_visible_keys(s, None)
    qk = rows * H * s * d_qk * itemsize      # q, k, dq, dk
    vo = rows * H * s * d_v * itemsize       # v, o, do, dv
    row = rows * H * s * 4                   # lse / delta, float32
    return {
        "fwd": {"flops": 2 * pairs * (d_qk + d_v),
                "bytes": 2 * qk + vo + vo + row},
        "dq": {"flops": 2 * pairs * (2 * d_qk + d_v),
               "bytes": 2 * qk + 2 * vo + 2 * row + qk},
        "dkv": {"flops": 2 * pairs * (2 * d_qk + 2 * d_v),
                "bytes": 2 * qk + 2 * vo + 2 * row + qk + vo},
    }


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel: ``flops`` and ``bytes`` of one call and the ``calls``
    a traced step runs of it on one chip.  dq and dkv: micro-batches x layers.
    The forward kernel: under recomputation a rematerialized layer reruns it,
    and the trace's time holds both runs, but only where its kind's scan is
    longer than one layer: the compiler merges the rerun of a scan of length 1
    with the first run (PERF.md section 7, PR 38), so the dense first layer's
    forward kernel runs once."""
    c = sizes(model)
    micro = int(traffic["micro_batches"])
    rows = int(traffic["global_batch_size"]) // micro // int(data_parallel)
    remat = bool(model.get("activations_checkpoint_granularity"))
    forward = sum(n * (2 if remat and n > 1 else 1) for n in (c["dense"], c["sparse"]))
    calls = {"fwd": micro * forward, "dq": micro * c["L"], "dkv": micro * c["L"]}
    return {kind: {**need, "calls": calls[kind]} for kind, need in flash_call(
        model, int(traffic["seq_length"]), rows).items()}
