"""Readings the limits of ``benchmark/limits.json`` are set from, on the chip
at a cell's own size, many seeds in one process:

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control fp8] [--fault half_batch] \\
        [--program-control precision.type=bf16SR] [--no-program]

For every seed: the reference in float32; the control (the reference with
every matmul operand rounded to ``--control``) held against it; with
``--fault``, the reference put in the program's place with that fault planted
in it, held against it too; the program held against it (a short window);
and, with ``--program-control``, the program with its own lower-precision
regime switched on.  Prints one JSON line per reading; PERF.md keeps the
table.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def half_batch(tokens: list) -> list:
    """Half of every step's batch left out and the mean taken over the rest:
    the second half of its rows replaced by the first, at the same shapes
    (the mean over rows that come twice is the mean over them once)."""
    out = []
    for step in tokens:                      # [micro, rows, seq]
        step, half = step.copy(), step.shape[1] // 2
        step[:, half:2 * half] = step[:, :half]
        out.append(step)
    return out


FAULTS = {"half_batch": half_batch}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--fault", default=None, choices=sorted(FAULTS),
                    help="planted in the reference put in the program's place")
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
        routed = checks.limits_for(cell.config_name, cell.root).get("routed_leaves")
        stands_in = []      # the reference in the program's place: (reading, how it is run)
        if args.control != "none":
            stands_in.append((f"control-{args.control}", {"quant": args.control}))
        if args.fault:
            stands_in.append((f"fault-{args.fault}", {"tokens": FAULTS[args.fault](tokens)}))
        if stands_in:
            ref = reference.run(model, model["optim"], clip, tokens, seed,
                                shard=checks.sharder(devices))
        for what, how in stands_in:
            t1 = time.perf_counter()
            got = reference.run(model, model["optim"], clip, how.get("tokens", tokens),
                                seed, quant=how.get("quant"),
                                shard=checks.sharder(devices))
            found = {k: v for k, (v, _) in checks.numbers(got, ref, routed).items()}
            found["leaves"] = {w: checks.leaf_gaps(got[w], ref[w])
                               for w in ("grad1", "dparam")}
            print(json.dumps({"reading": what, "seed": seed,
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
