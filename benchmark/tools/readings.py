"""Readings the limits of ``benchmark/limits.json`` are set from, on the chip
at a cell's own size, many seeds in one process:

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control fp8] [--program-control precision.type=bf16SR] [--no-program]

For every seed: the reference in float32; the control (the reference with
every matmul operand rounded to ``--control``) held against it; the program
held against it (a short window); and, with ``--program-control``, the
program with its own lower-precision regime switched on.  Prints one JSON
line per reading; PERF.md keeps the table.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--program-control", default=None,
                    help="dotted override, key=value, of the program's own path")
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark.harness import cell as cells
    from benchmark.harness import check as checks
    from benchmark.harness import drive

    cell = cells.load_cell(args.workload)
    reference = cell.reference
    devices = jax.devices()[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        as_run = drive.merged_config(
            cell, drive.overrides_for(cell, seed, False, drive.WORK / "readings"))
        model = as_run["model"]
        tokens = drive.check_tokens(cell, model, seed)
        clip = as_run["trainer"].get("gradient_clip_val")
        if args.control != "none":
            t1 = time.perf_counter()
            ref = reference.run(model, model["optim"], clip, tokens, seed,
                                shard=checks.sharder(devices))
            ctl = reference.run(model, model["optim"], clip, tokens, seed,
                                quant=args.control, shard=checks.sharder(devices))
            routed = checks.limits_for(
                cell.config_name, cell.root).get("routed_leaves")
            found = {k: v for k, (v, _) in checks.numbers(ctl, ref, routed).items()}
            found["leaves"] = {w: checks.leaf_gaps(ctl[w], ref[w])
                               for w in ("grad1", "dparam")}
            print(json.dumps({"reading": f"control-{args.control}", "seed": seed,
                              "seconds": time.perf_counter() - t1, **found}),
                  flush=True)
        runs = [] if args.no_program else [("program", None)]
        if args.program_control:
            key, _, value = args.program_control.partition("=")
            runs.append((f"program-control-{value}", {key: value}))
        for what, ov in runs:
            try:
                res = drive.run_cell(
                    cell, seed=seed, seconds=args.seconds, trace=False,
                    t_process=time.perf_counter(),
                    require_tpu=not args.allow_cpu, overrides=ov)
                print(json.dumps({"reading": what, "seed": seed,
                                  "correct": res["correct"], **res["compared"]}),
                      flush=True)
            except Exception as e:  # noqa: BLE001 — a control that crashes has failed
                print(json.dumps({"reading": what, "seed": seed,
                                  "crashed": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)


if __name__ == "__main__":
    main()
