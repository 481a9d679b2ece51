#!/usr/bin/env python
"""KV-cache decode throughput micro-benchmark (single chip).

The training-side counterpart is ``bench.py`` (the driver metric); this
measures the inference path the SFT-evaluation harness uses
(``models/decode.py``: prefill + single-token decode steps), reported as
steady-state decode tokens/sec and prefill tokens/sec.

Llama-3-8B per-layer shapes with the layer count scaled to fit the chip in
bf16 (same proxy convention as bench.py).  Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--new-tokens", type=int, default=128)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    from neuronx_distributed_training_tpu.models import decode, llama
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

    if on_tpu:
        hbm = dev.memory_stats()["bytes_limit"]
        h, ffn, nh, nkv, vocab = 4096, 14336, 32, 8, 128256
        per_layer = h * (nh + 2 * nkv) * (h // nh) + nh * (h // nh) * h + 3 * h * ffn
        # 35% of HBM for bf16 params; the rest holds the KV cache and
        # activations
        layers = args.layers or max(
            1, min(32, int((hbm * 0.35 / 2 - vocab * h) // per_layer))
        )
        cfg = llama.LlamaConfig(
            vocab_size=vocab, hidden_size=h, intermediate_size=ffn,
            num_layers=layers, num_attention_heads=nh, num_kv_heads=nkv,
            max_position_embeddings=args.prompt_len + args.new_tokens,
            rope_theta=500000.0, tie_word_embeddings=True,
            attention_impl="flash",
        )
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=352,
            num_layers=args.layers or 2, num_attention_heads=8, num_kv_heads=4,
            max_position_embeddings=args.prompt_len + args.new_tokens,
            tie_word_embeddings=True,
        )
    policy = DtypePolicy.from_precision_config(
        {"type": "bf16SR"} if on_tpu else {"type": "fp32"}
    )
    key = jax.random.PRNGKey(0)
    params = llama.init_params(key, cfg, policy)
    b, plen, n = args.batch, args.prompt_len, args.new_tokens
    total = plen + n
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, plen), 3, cfg.vocab_size)

    prefill = jax.jit(lambda p, i: decode.prefill(p, i, cfg, policy, max_len=total))
    step = jax.jit(lambda p, c, t, pos: decode.decode_step(p, c, t, pos, cfg, policy))

    # warmup/compile
    h_out, cache = prefill(params, ids)
    tok = jnp.full((b,), 5, jnp.int32)
    pos = jnp.full((b,), plen, jnp.int32)
    _, cache_w = step(params, cache, tok, pos)
    jax.block_until_ready((h_out, cache_w["k"]))

    # fresh inputs per run; block_until_ready is the timing barrier (it
    # suffices on a real runtime)
    reps = 3
    t0 = time.perf_counter()
    for r in range(reps):
        ids_r = jax.random.randint(
            jax.random.PRNGKey(100 + r), (b, plen), 3, cfg.vocab_size
        )
        h_out, cache = prefill(params, ids_r)
        jax.block_until_ready(h_out)
    prefill_s = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for i in range(n):
        logits, cache = step(params, cache, tok, pos + i)
    jax.block_until_ready(logits)
    decode_s = time.perf_counter() - t0

    out = {
        "metric": "llama3_8B_cached_decode",
        "value": round(b * n / decode_s, 1),
        "unit": "decode_tokens_per_sec",
        "prefill_tokens_per_sec": round(b * plen / prefill_s, 1),
        "ms_per_decode_step": round(decode_s / n * 1000, 3),
        "batch": b, "prompt_len": plen, "new_tokens": n,
        "num_layers": cfg.num_layers,
        "device": dev.device_kind,
        "note": "layer count scaled to single-chip HBM (bench.py convention)",
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
