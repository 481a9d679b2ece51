#!/usr/bin/env python
"""Graph-contract CLI — the compile-artifact regression ratchet.

For each config this AOT-lowers the (shrunk) train step on abstract inputs,
extracts its **contract fingerprint** (collective census by kind×axis-group
with per-collective provenance, donation coverage map, ``memory_analysis()``
bytes, matmul dtype census) and compares it against the committed golden
snapshot under ``neuronx_distributed_training_tpu/analysis/contracts/``:

    python tools/graph_contract.py --check --all-examples
    python tools/graph_contract.py --check --config examples/conf/foo.yaml
    python tools/graph_contract.py --update-contracts --all-examples
    python tools/graph_contract.py --update-contracts --config foo.yaml \
        --justify "added fused CE: +2 tp all-reduces"

``--check`` fails (exit 1) on any regression: a collective class that grew,
a GSPMD-inserted reshard no declared source explains, a donated leaf that
lost its alias, a matmul dtype upcast, or resident bytes beyond tolerance —
each explained in config-level terms naming the offending HLO op
(docs/static_analysis.md "Graph contracts").

``--update-contracts`` is the ratchet's write side: shrinking fingerprints
commit silently; GROWTH refuses to commit without ``--justify`` (recorded
in-file), and unattributed collectives become named waivers.

``--jobs N`` fingerprints configs in parallel processes (the sweep is
embarrassingly parallel); output order stays deterministic and ``--json``
keeps the shared single-last-line contract (tools/_jsonout.py).
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # tools/ siblings


def _fingerprint_worker(args: tuple) -> dict:
    """One config -> fingerprint dict (or an ``error`` payload).  Runs in a
    worker process under --jobs: the parent exported XLA_FLAGS/JAX_PLATFORMS
    before the pool spawned, so each worker sizes its own CPU world."""
    path, shrink = args
    import jax

    jax.config.update("jax_platforms", "cpu")
    from neuronx_distributed_training_tpu.analysis.graph_contract import (
        ContractError,
        fingerprint_config,
    )

    try:
        return {"path": path, "fingerprint": fingerprint_config(
            path, shrink=shrink)}
    except ContractError as e:
        return {"path": path, "error": str(e)}
    except Exception as e:  # noqa: BLE001 — a worker must return, not die
        return {"path": path, "error": f"{type(e).__name__}: {e}"}


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", action="append", default=[],
                    help="YAML config to fingerprint (repeatable)")
    ap.add_argument("--all-examples", action="store_true",
                    help="every examples/conf/*.yaml")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true", default=True,
                      help="diff against the committed contract (default)")
    mode.add_argument("--update-contracts", action="store_true",
                      help="rewrite the committed snapshot(s); growth "
                           "requires --justify")
    ap.add_argument("--justify", metavar="TEXT",
                    help="in-file justification for contract growth "
                         "(--update-contracts)")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="fingerprint N configs in parallel processes "
                         "(default 1: serial)")
    ap.add_argument("--no-shrink", dest="shrink", action="store_false",
                    help="fingerprint at true config size (needs a device "
                         "world that large)")
    ap.add_argument("--memory-tolerance", type=float, default=None,
                    help="resident-bytes growth fraction that fails "
                         "(default 0.10)")
    ap.add_argument("--contracts-dir", metavar="DIR",
                    help="snapshot directory override (default: the "
                         "committed analysis/contracts/)")
    ap.add_argument("--json", metavar="PATH",
                    help="machine-readable report ('-' for stdout, "
                         "guaranteed last line)")
    args = ap.parse_args()

    configs = list(args.config)
    if args.all_examples:
        import glob

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        configs += sorted(glob.glob(os.path.join(here, "examples/conf/*.yaml")))
    if not configs:
        ap.error("nothing to do: pass --config and/or --all-examples")

    # A static CPU analysis, pinned to CPU: neither this process nor a --jobs
    # worker may take a chip.  Size the virtual device world BEFORE any jax
    # initializes (the env is inherited across the spawn).
    from preflight_audit import _required_world

    world = max(_required_world(configs, args.shrink), 8)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={world}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    work = [(p, args.shrink) for p in configs]
    if args.jobs > 1 and len(work) > 1:
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor

        with ProcessPoolExecutor(
                max_workers=min(args.jobs, len(work)),
                mp_context=mp.get_context("spawn")) as pool:
            results = list(pool.map(_fingerprint_worker, work))
    else:
        results = [_fingerprint_worker(w) for w in work]

    from neuronx_distributed_training_tpu.analysis import graph_contract as gc
    from neuronx_distributed_training_tpu.analysis.report import AuditReport

    tol = (args.memory_tolerance if args.memory_tolerance is not None
           else gc.MEMORY_TOLERANCE)
    cdir = Path(args.contracts_dir) if args.contracts_dir else None
    failed = False
    out: dict = {"reports": []}
    for res in results:  # input order: deterministic merged output
        name = Path(res["path"]).name
        if "error" in res:
            rep = AuditReport(config=name)
            rep.add("GC000", "error", res["error"],
                    hint="the config lowers no further; fix it before "
                         "contracting")
            print(rep.format())
            print()
            out["reports"].append(rep.to_dict())
            failed = True
            continue
        fp = res["fingerprint"]
        if args.update_contracts:
            try:
                path, rep = gc.update_contract(
                    res["path"], fp, justify=args.justify,
                    memory_tolerance=tol, contracts_dir=cdir)
                drift = rep.by_severity() or "no drift"
                print(f"contract [{name}]: updated -> {path} ({drift})")
            except gc.ContractError as e:
                print(f"contract [{name}]: REFUSED: {e}")
                failed = True
                out["reports"].append(
                    {"config": name, "verdict": "error",
                     "refused": str(e)})
                continue
        else:
            rep = gc.check_contract(res["path"], fp,
                                    memory_tolerance=tol,
                                    contracts_dir=cdir)
            verdict = rep.worst() or "clean"
            unattr = sum(v["count"] for v in
                         gc.unattributed_entries(fp).values())
            total = sum(v["count"] for v in
                        (fp.get("collectives") or {}).values())
            print(f"contract [{name}]: {verdict} "
                  f"({total} collectives, {total - unattr} attributed)")
            if rep.findings:
                print(rep.format())
            print()
            failed |= rep.failed("error")
        rep_dict = rep.to_dict()
        rep_dict["fingerprint"] = fp
        out["reports"].append(rep_dict)

    if args.json:
        from _jsonout import write_json

        write_json(out, args.json)

    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
