"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for, no children.  The last line of
stdout is the result object; every line before it is a record of the run.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: copy the raw .xplane.pb here")
    args = ap.parse_args()

    from benchmark.harness import cell as cells
    from benchmark.harness import drive

    result = drive.run_cell(
        cells.load_cell(args.workload), seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS,
        keep_xplane=Path(args.keep_trace) if args.keep_trace else None)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
