"""Operations and bytes a decoder whose attention keys a learned indexer
chooses, over softmax-routed experts (``architecture: keye``), needs, from
shapes alone.  Conventions are ``benchmark/flops.py``'s: a matmul of ``[m, k]
x [k, n]`` is ``2 m k n``; backward = 2 x forward, so a trained token needs 3
x its forward operations; recomputed operations are not required operations;
embedding lookups, norms, RoPE, softmax, the router's softmax and top-k, the
selection (a threshold and a mask: compares, no matmul), the KL and the
optimizer are not counted.

What the learned selection changes, all REQUIRED work and no more:

- the main attention scores and weighs the SELECTED pairs alone: a query at
  ``t`` keeps ``min(topk, t + 1)`` keys, so a token meets ``(k (k + 1) / 2 +
  (T - k) k) / T`` pairs in the mean (1792.1 of the causal 4096.5 at ``T``
  8192, ``k`` 2048: 43.75 %).  A kernel or a chunk of XLA that forms every
  causal pair and masks is not credited with the pairs it throws away;
- the indexer's three projections read a detached input: forward and the
  weights' gradient, no gradient to the input, so 4 x their parameters a
  token where a projection inside the stream costs 6;
- the index scores run over EVERY causal pair (``Hi x di`` a pair), forward
  and both operands' gradients: 3 x;
- a second ``Q K^T`` that a program runs to rebuild the main attention's
  probabilities for the indexer's loss is recomputation: nothing.

Of the routed experts only the slots THIS chip holds count
(``num_experts_per_tok x held / num_experts`` a token when routing is even).
"""

from __future__ import annotations

from typing import Any, Mapping


def sizes(model: Mapping[str, Any]) -> dict:
    experts = int(model["num_experts"])
    held = model.get("num_experts_held") or [0, experts]
    heads = int(model["num_attention_heads"])
    sa = dict(model.get("sa_config") or {})
    return {
        "h": int(model["hidden_size"]), "L": int(model["num_hidden_layers"]),
        "H": heads, "G": int(model.get("num_key_value_heads") or heads),
        "d": int(model.get("head_dim") or int(model["hidden_size"]) // heads),
        "V": int(model["vocab_size"]), "E": experts,
        "k": int(model["num_experts_per_tok"]), "held": int(held[1]) - int(held[0]),
        "fe": int(model["moe_intermediate_size"]),
        "Hi": int(sa.get("indexer_num_heads", 16)), "di": int(sa.get("indexer_head_dim", 64)),
        "topk": int(sa.get("topk", 2048)),
    }


def kept_keys(seq_len: int, topk: int) -> float:
    """Keys a query keeps, the mean over a sequence's queries."""
    k = min(int(topk), int(seq_len))
    return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token (module
    docstring); ``kept_pairs_share`` the selected pairs over the causal ones."""
    c = sizes(model)
    h, H, G, d, L = c["h"], c["H"], c["G"], c["d"], c["L"]
    slots = c["k"] * c["held"] / c["E"]
    kept, causal = kept_keys(seq_len, c["topk"]), (seq_len + 1) / 2
    index_dims = c["Hi"] * c["di"]
    out = {
        "qkv_and_o": L * 6.0 * (h * (H + 2 * G) * d + H * d * h),
        "selected_scores": L * 3.0 * 2 * H * 2 * d * kept,
        "indexer_projections": L * 4.0 * h * (index_dims + c["di"] + c["Hi"]),
        "index_scores": L * 3.0 * 2 * index_dims * causal,
        "router": L * 6.0 * h * c["E"],
        "held_experts": L * 6.0 * slots * 3 * h * c["fe"],
        "head": 6.0 * h * c["V"],
    }
    return {"total": sum(out.values()), **out, "held_slots_per_token": slots,
            "kept_pairs_share": kept / causal}


def flash_call(model: Mapping[str, Any], seq_len: int, rows: int, itemsize: int = 2) -> dict:
    """Operations and bytes one call of each attention kernel kind needs on
    ``rows`` sequences, whatever implements it: the selected pairs alone (per
    pair and head: the forward scores over ``d`` and weighs ``d``; dq
    recomputes the scores, then ``dO V^T`` and ``dS K``; dkv recomputes the
    scores, then ``P^T dO``, ``dO V^T`` and ``dS^T Q``); q, o and their
    cotangents for every query head, k, v and theirs for every key/value
    head, each read or written once."""
    c = sizes(model)
    s, H, G, d = int(seq_len), c["H"], c["G"], c["d"]
    pairs = rows * H * s * kept_keys(s, c["topk"])
    qo = rows * H * s * d * itemsize
    kv = rows * G * s * d * itemsize
    row = rows * H * s * 4
    return {
        "fwd": {"flops": 2 * pairs * 2 * d, "bytes": 2 * qo + 2 * kv + row},
        "dq": {"flops": 2 * pairs * 3 * d, "bytes": 3 * qo + 2 * kv + 2 * row},
        "dkv": {"flops": 2 * pairs * 4 * d, "bytes": 2 * qo + 4 * kv + 2 * row},
    }


def _rows_and_runs(model, traffic, data_parallel) -> tuple:
    micro = int(traffic["micro_batches"])
    rows = int(traffic["global_batch_size"]) // micro // int(data_parallel)
    remat = bool(model.get("activations_checkpoint_granularity"))
    return micro, rows, 2 if remat else 1


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per attention kernel kind: ``flops`` and ``bytes`` of one call and the
    ``calls`` a traced step runs on one chip: micro-batches x layers each (the
    forward kernel's outputs are kept across a rematerialized layer, so its
    rerun does not call it again).  The program's kernels form every causal
    pair of the blocks they visit and mask the unselected ones; the need here
    is the selected pairs', so the share of the roofline they reach is at
    most the kept pairs' share of the causal ones."""
    c = sizes(model)
    micro, rows, _ = _rows_and_runs(model, traffic, data_parallel)
    calls = {"fwd": micro * c["L"], "dq": micro * c["L"], "dkv": micro * c["L"]}
    return {kind: {**need, "calls": calls[kind]} for kind, need in flash_call(
        model, int(traffic["seq_length"]), rows).items()}


def select_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """The selection of one layer application: no matmul; its least time is
    ONE read of the float32 index scores of the causal pairs, ``rows x T^2 / 2
    x 4`` bytes (what it writes, and the passes a sort or a bisection makes
    over them, are the implementation's).  ``calls``: layers, twice under
    recomputation (the trace's time under ``attention/select`` holds the
    rerun)."""
    c = sizes(model)
    micro, rows, runs = _rows_and_runs(model, traffic, data_parallel)
    s = int(traffic["seq_length"])
    return {"select": {"flops": 0.0, "bytes": rows * s * s / 2 * 4,
                       "calls": micro * c["L"] * runs}}
