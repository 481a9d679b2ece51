#!/usr/bin/env python
"""Checkpoint converter CLI: HF <-> native Orbax checkpoints.

The reference's converter surface (``checkpoint_converter_scripts/
checkpoint_converter.py:1-53``: HF full-state <-> sharded, both directions,
Llama + Mixtral):

    python examples/checkpoint_converter.py \
        --model llama --direction hf2native \
        --config examples/conf/hf_llama3_8B_config.yaml \
        --input /path/to/hf_checkpoint_dir --output /path/to/native_ckpt

native2hf writes a ``model.safetensors`` (or .npz fallback) HF state dict.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _load_nnm_state(path: str, tp: int, pp: int, num_layers: int, glu: bool):
    """Load a NeMo-Megatron checkpoint: either a single state-dict file or the
    rank-sharded ``tp_rank_XX_pp_rank_XXX/model_optim_rng.ckpt`` layout the
    reference converter walks (``nnm_model_ckpt_to_nxdt...py:88-111``)."""
    from neuronx_distributed_training_tpu.tools import convert, convert_megatron

    p = Path(path)
    if p.is_file():
        return convert.load_torch_state_dict(str(p))
    shards = {}
    for r in range(tp):
        for s in range(pp):
            name = (f"tp_rank_{r:02d}_pp_rank_{s:03d}" if pp > 1
                    else f"mp_rank_{r:02d}")
            ck = p / name / "model_optim_rng.ckpt"
            if not ck.exists():
                ck = p / name / "model_weights.ckpt"
            import torch

            sd = torch.load(str(ck), map_location="cpu", weights_only=False)
            sd = sd.get("state_dict", sd)
            shards[(r, s)] = {
                k: v.float().numpy() for k, v in sd.items() if hasattr(v, "numpy")
            }
    return convert_megatron.merge_nnm_shards(
        shards, tp=tp, pp=pp, num_layers=num_layers, glu=glu
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=["llama", "mixtral", "gpt"], default="llama")
    ap.add_argument("--direction",
                    choices=["hf2native", "native2hf", "nnm2native", "native2nnm"],
                    required=True)
    ap.add_argument("--config", required=True, help="YAML config (reference schema)")
    ap.add_argument("--input", required=True)
    ap.add_argument("--output", required=True)
    ap.add_argument("--step", type=int, default=0,
                    help="checkpoint step number to write/read (native side)")
    ap.add_argument("--tp", type=int, default=1,
                    help="TP degree of a sharded NNM checkpoint dir")
    ap.add_argument("--pp", type=int, default=1,
                    help="PP degree of a sharded NNM checkpoint dir")
    args = ap.parse_args()

    import orbax.checkpoint as ocp

    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.models import llama as llama_mod
    from neuronx_distributed_training_tpu.tools import convert

    cfg_yaml = load_config(args.config)
    model_block = dict(cfg_yaml.get("model", {}) or {})
    ds_block = dict(cfg_yaml.get("distributed_strategy", {}) or {})

    if args.direction in ("nnm2native", "native2nnm") or args.model == "gpt":
        from neuronx_distributed_training_tpu.models import gpt as gpt_mod
        from neuronx_distributed_training_tpu.tools import convert_megatron

        cfg = gpt_mod.GPTConfig.from_config(model_block, ds_block)
        to_native = lambda sd: convert_megatron.megatron_gpt_to_native(sd, cfg)
        to_hf = lambda p, layer_layout=None: convert_megatron.native_to_megatron_gpt(
            p, cfg, layer_layout=layer_layout)
    elif args.model == "llama":
        cfg = llama_mod.LlamaConfig.from_config(model_block, ds_block)
        to_native = lambda sd: convert.hf_llama_to_native(sd, cfg)
        to_hf = lambda p, layer_layout=None: convert.native_to_hf_llama(
            p, cfg, layer_layout=layer_layout)
    else:
        from neuronx_distributed_training_tpu.models import mixtral as mixtral_mod

        cfg = mixtral_mod.MixtralConfig.from_config(model_block, ds_block)
        to_native = lambda sd: convert.hf_mixtral_to_native(sd, cfg)
        to_hf = lambda p, layer_layout=None: convert.native_to_hf_mixtral(
            p, cfg, layer_layout=layer_layout)

    out = Path(args.output)
    if args.direction in ("hf2native", "nnm2native"):
        if args.direction == "nnm2native":
            state = _load_nnm_state(
                args.input, args.tp, args.pp,
                num_layers=int(model_block.get("num_layers", 12)),
                glu=str(model_block.get("activation", "gelu")) in
                    ("swiglu", "geglu", "reglu"),
            )
        else:
            state = convert.load_torch_state_dict(args.input)
        params = to_native(state)
        with ocp.CheckpointManager(out.absolute()) as mgr:
            mgr.save(args.step, args=ocp.args.Composite(
                params=ocp.args.StandardSave(params)))
            mgr.wait_until_finished()
        print(f"wrote native checkpoint: {out}/{args.step}/params")
    else:
        with ocp.CheckpointManager(Path(args.input).absolute()) as mgr:
            step = args.step or mgr.latest_step()
            layout = None
            try:
                meta = mgr.restore(step, args=ocp.args.Composite(
                    meta=ocp.args.JsonRestore()))["meta"]
                layout = (meta or {}).get("layer_layout")
            except Exception:
                pass  # metadata-less checkpoint: shape heuristic fallback
            restored = mgr.restore(step, args=ocp.args.Composite(
                params=ocp.args.StandardRestore()))
        sd = to_hf(restored["params"], layer_layout=layout)
        out.mkdir(parents=True, exist_ok=True)
        try:
            from safetensors.numpy import save_file

            save_file(sd, str(out / "model.safetensors"))
            print(f"wrote {out}/model.safetensors ({len(sd)} tensors)")
        except ImportError:
            import numpy as np

            np.savez(out / "model.npz", **sd)
            print(f"wrote {out}/model.npz ({len(sd)} tensors)")


if __name__ == "__main__":
    main()
