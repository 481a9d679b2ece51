#!/usr/bin/env python
"""SFT evaluation CLI: checkpoint + eval records -> ROUGE-L / F1 / EM.

The runnable counterpart of the reference's ``examples/sft_evaluation/
evaluate.py`` (prompt templates, generation knobs, metric factory), driving
the KV-cached decoder:

    python examples/run_sft_evaluation.py \
        --config examples/conf/hf_llama3_8B_SFT_config.yaml \
        --checkpoint /path/to/native_ckpt --step 500 \
        --data /path/to/eval.jsonl --tokenizer /path/to/tok \
        --prompt-template "{input}" --max-new-tokens 256 \
        [--temperature 0.7 --top-p 0.9 --top-k 50]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--checkpoint", required=True, help="native Orbax ckpt dir")
    ap.add_argument("--step", type=int, default=0, help="0 = latest")
    ap.add_argument("--data", required=True, help="jsonl/json/arrow eval records")
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--prompt-template", default="{input}")
    ap.add_argument("--target-field", default="output")
    ap.add_argument("--max-new-tokens", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--limit", type=int, default=None)
    args = ap.parse_args()

    import jax
    import numpy as np
    import orbax.checkpoint as ocp
    from transformers import AutoTokenizer

    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.data.modules import load_alignment_records
    from neuronx_distributed_training_tpu.models import decode, generate as gen
    from neuronx_distributed_training_tpu.tools.evaluate import (
        render_prompt,
        score,
    )
    from neuronx_distributed_training_tpu.models.family import resolve
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

    cfg = load_config(args.config)
    policy = DtypePolicy.from_precision_config(cfg.get("precision", {}))
    _, model_cfg = resolve(cfg)
    tok = AutoTokenizer.from_pretrained(args.tokenizer)
    eos = tok.eos_token_id or 0

    with ocp.CheckpointManager(Path(args.checkpoint).absolute()) as mgr:
        step = args.step or mgr.latest_step()
        params = mgr.restore(step, args=ocp.args.Composite(
            params=ocp.args.StandardRestore()))["params"]

    records = load_alignment_records(args.data)
    if args.limit:
        records = records[: args.limit]

    preds, refs = [], []
    for i in range(0, len(records), args.batch_size):
        batch = records[i:i + args.batch_size]
        prompts = [tok.encode(render_prompt(args.prompt_template, r))
                   for r in batch]
        ids, lens = gen.pad_prompts(prompts, pad_id=eos)
        out = decode.generate_cached(
            params, model_cfg, policy, ids, lens,
            max_new_tokens=args.max_new_tokens, eos_id=eos, pad_id=eos,
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            key=jax.random.PRNGKey(i),
        )
        out = np.asarray(out)
        for b, r in enumerate(batch):
            gen_ids = out[b, int(lens[b]):]
            gen_ids = gen_ids[gen_ids != eos]
            preds.append(tok.decode(gen_ids))
            refs.append(str(r[args.target_field]))
        print(f"generated {min(i + args.batch_size, len(records))}/{len(records)}",
              file=sys.stderr)

    print(json.dumps(score(preds, refs), indent=2))


if __name__ == "__main__":
    main()
