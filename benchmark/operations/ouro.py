"""Operations and bytes the looped decoder (``architecture: ouro``) needs,
from shapes alone.  Conventions are ``benchmark/flops.py``'s: a matmul of
``[m, k] x [k, n]`` is ``2 m k n``; backward = 2 x forward, so a trained token
needs 3 x its forward operations; recomputed operations are not required
operations; embedding lookups, norms, RoPE, softmax, the exit mixture and the
optimizer are not counted.

What the loop changes: the T = ``total_ut_steps`` passes multiply the layers'
matmuls, the heads (one a pass), the gate's dot product and the attention;
parameters do not multiply, work does.
"""

from __future__ import annotations

from typing import Any, Mapping

from benchmark import flops


def passes(model: Mapping[str, Any]) -> int:
    return int(model.get("total_ut_steps", 4))


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> dict:
    """Required forward+backward operations per trained token: ``total`` =
    6 T (L x layer matmul parameters + head + gate) + 3 T L x causal attention
    at the mean visible keys."""
    d = flops.model_dims(model)
    p = flops.matmul_params_per_token(d)
    t = passes(model)
    keys = flops.mean_visible_keys(seq_len, d["window"])
    stack = 6 * t * d["layers"] * p["layer"]
    heads = 6 * t * p["head"]
    gate = 6 * t * d["hidden"]
    attention = 3 * t * d["layers"] * 4 * d["heads"] * d["head_dim"] * keys
    return {"total": stack + heads + gate + attention, "stack": stack, "heads": heads,
            "gate": gate, "attention": attention, "passes": t, "mean_keys": keys}


def kernel_calls(model: Mapping[str, Any], traffic: Mapping[str, Any],
                 data_parallel: int) -> dict:
    """Per flash kernel: ``flops`` and ``bytes`` of one causal call
    (``flops.flash_call`` on the rows one chip holds of a micro-batch) and the
    ``calls`` a step makes of it: micro-batches x layers x passes for the two
    backward kernels.  The forward kernel runs twice as often: every layer
    application is rematerialized in backward (``activations_checkpoint_
    granularity`` ``full`` or ``selective``: the loop recomputes a pass either
    way, ``models/ouro.py``), which reruns it, and the trace's time holds both
    runs.  Without recomputation it runs once."""
    rows = (int(traffic["global_batch_size"]) // int(traffic["micro_batches"])
            // int(data_parallel))
    applications = (int(traffic["micro_batches"]) * int(model["num_layers"])
                    * passes(model))
    rerun = 2 if model.get("activations_checkpoint_granularity") else 1
    calls = {"fwd": rerun * applications, "dq": applications, "dkv": applications}
    return {kind: {**need, "calls": calls[kind]} for kind, need in flops.flash_call(
        model, int(traffic["seq_length"]), rows).items()}
