"""Operations and bytes the mixed stack (``architecture: laguna``) needs, from
shapes alone.  Conventions are ``benchmark/flops.py``'s: a matmul of ``[m, k]
x [k, n]`` is ``2 m k n``; backward = 2 x forward, so a trained token needs 3
x its forward operations; recomputed operations are not required operations;
a causal query sees ``min(i + 1, window)`` keys; embedding lookups, norms,
RoPE, softmax, the sigmoid of the head gate and the optimizer are not counted.

What the mix changes: every layer counts by its own kind (query heads by
attention type, keys capped by the window in sliding layers only, a dense MLP
or router + shared expert + routed experts), and of the routed experts only
the slots THIS chip holds count: a token fills ``num_experts_per_tok`` slots
over all experts, of which ``held / num_experts`` fall here when routing is
even.  The rows really received move with the data (``moe/held_rows_share``);
the count does not.
"""

from __future__ import annotations

from typing import Any, Mapping

from benchmark import flops

WINDOWED = "sliding_attention"


def sizes(model: Mapping[str, Any]) -> dict:
    held = model.get("num_experts_held") or [0, int(model["num_experts"])]
    return {
        "h": int(model["hidden_size"]), "f": int(model["intermediate_size"]),
        "d": int(model["head_dim"]), "nkv": int(model["num_key_value_heads"]),
        "heads": {t: int(n) for t, n in model["num_attention_heads_per_layer"].items()},
        "kinds": list(zip(model["layer_types"], model["mlp_layer_types"])),
        "window": int(model["sliding_window"]), "V": int(model["vocab_size"]),
        "E": int(model["num_experts"]), "k": int(model["num_experts_per_tok"]),
        "held": int(held[1]) - int(held[0]),
        "fe": int(model["moe_intermediate_size"]),
        "fs": int(model.get("shared_expert_intermediate_size", 0) or 0),
    }


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token: ``total`` = 6 x
    the matmul parameters a token meets (projections and head gate by kind,
    dense MLP, router, shared expert, the held experts' expected slots, head)
    + 3 x the score and context matmuls at each kind's mean visible keys."""
    c = sizes(model)
    h, d = c["h"], c["d"]
    slots = c["k"] * c["held"] / c["E"]
    out = {"projections": 0.0, "scores": 0.0, "dense_mlp": 0.0, "router": 0.0,
           "shared_expert": 0.0, "held_experts": 0.0}
    for attention, mlp in c["kinds"]:
        nh = c["heads"][attention]
        keys = flops.mean_visible_keys(seq_len, c["window"] if attention == WINDOWED else None)
        out["projections"] += 6 * (h * (nh + 2 * c["nkv"]) * d + nh * d * h + h * nh)
        out["scores"] += 3 * 4 * nh * d * keys
        if mlp == "dense":
            out["dense_mlp"] += 6 * 3 * h * c["f"]
        else:
            out["router"] += 6 * h * c["E"]
            out["shared_expert"] += 6 * 3 * h * c["fs"]
            out["held_experts"] += 6 * slots * 3 * h * c["fe"]
    out["head"] = 6.0 * h * c["V"]
    return {"total": sum(out.values()), **out, "held_slots_per_token": slots}


def kernel_calls_by_type(model: Mapping[str, Any], traffic: Mapping[str, Any],
                         data_parallel: int) -> dict:
    """Attention type -> flash kernel -> ``flops`` and ``bytes`` of one call of
    that type's shape (``flops.flash_call``: causal, window-capped in sliding
    layers) and the ``calls`` a step makes of it on one chip: micro-batches x
    layers of the type, the forward kernel twice under recomputation (a
    rematerialized layer reruns it and the trace's time holds both runs)."""
    c = sizes(model)
    rows = (int(traffic["global_batch_size"]) // int(traffic["micro_batches"])
            // int(data_parallel))
    rerun = 2 if model.get("activations_checkpoint_granularity") else 1
    found = {}
    for attention in sorted({a for a, _ in c["kinds"]}):
        layers = sum(1 for a, _ in c["kinds"] if a == attention)
        shape = {"hidden_size": c["h"], "intermediate_size": c["f"], "num_layers": layers,
                 "num_attention_heads": c["heads"][attention],
                 "num_key_value_heads": c["nkv"], "head_dim": c["d"], "vocab_size": c["V"],
                 "sliding_window": c["window"] if attention == WINDOWED else None}
        n = int(traffic["micro_batches"]) * layers
        calls = {"fwd": rerun * n, "dq": n, "dkv": n}
        found[attention] = {
            kind: {**need, "calls": calls[kind]} for kind, need in flops.flash_call(
                shape, int(traffic["seq_length"]), rows).items()}
    return found


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel, over both call shapes: the ``calls`` a step makes and
    the mean ``flops`` and ``bytes`` of one, so that ``calls`` x either is the
    step's required work of that kernel (every call is compute-bound, so the
    sum of the least times is the least time of the sums)."""
    by_type = kernel_calls_by_type(model, traffic, data_parallel)
    out = {}
    for kind in ("fwd", "dq", "dkv"):
        calls = sum(t[kind]["calls"] for t in by_type.values())
        out[kind] = {"calls": calls, **{
            what: sum(t[kind][what] * t[kind]["calls"] for t in by_type.values()) / calls
            for what in ("flops", "bytes")}}
    return out
