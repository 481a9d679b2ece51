#!/usr/bin/env python
"""Pre-flight static audit CLI — the gate that runs before a device-hour.

Two layers (docs/static_analysis.md has the rule catalogue):

- **graph audit** (``--config``): AOT-lowers the train step for a YAML config
  on abstract inputs — no TPU, no data files, no arrays — and checks the
  compiled artifact against the config's declared contracts (donation
  aliased, collective census vs parallelism, replication budget, precision).
- **source lint** (``--lint``): the jaxlint AST pass over the package with
  its committed ratchet baseline; NEW findings (and stale baseline entries)
  fail.

Usage:

    python tools/preflight_audit.py --config examples/conf/hf_llama3_8B_config.yaml
    python tools/preflight_audit.py --lint
    python tools/preflight_audit.py --all-examples --lint --json audit.json
    python tools/preflight_audit.py --lint --update-baseline   # rebaseline

Exit code 1 when any finding reaches ``--fail-on`` severity (default
``error``; lint ratchet failures always count).  ``--json`` writes the full
machine-readable report; the terminal always gets the human form.

The graph audit shrinks large configs by default (degrees clamp to 2, dims
to the smallest shapes satisfying them — the *structure* under audit is
preserved); ``--no-shrink`` audits at the config's true size, which needs a
real (or forced-host) device world that large.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # tools/_jsonout


def _required_world(config_paths: list[str], shrink: bool) -> int:
    """Device count the audits need — computed from raw YAML before jax
    initializes, so the CPU world can still be sized via XLA_FLAGS."""
    import yaml

    from neuronx_distributed_training_tpu.config import loader as _loader

    world = 1
    for p in config_paths:
        try:
            with open(p) as f:
                raw = yaml.safe_load(f) or {}
            raw = _loader._resolve_tree(raw, raw)
            ds = dict(raw.get("distributed_strategy") or {})

            def deg(key):
                try:
                    v = int(ds.get(key) or 1)
                except (TypeError, ValueError):
                    v = 1
                return min(v, 2) if shrink else v

            base = (deg("tensor_model_parallel_size")
                    * deg("pipeline_model_parallel_size")
                    * deg("context_parallel_size")
                    * deg("expert_model_parallel_size"))
            world = max(world, base * 2)
        except Exception:  # noqa: BLE001 — sizing is best-effort; audit reports
            continue
    return world


def _audit_worker(args: tuple) -> dict:
    """Graph-audit one config in a worker process (--jobs).  The parent
    exported XLA_FLAGS / JAX_PLATFORMS before the pool spawned, so each
    worker initializes its own correctly-sized CPU world; results carry the
    pre-rendered text so the parent can merge output deterministically."""
    path, shrink, slack, contracts = args
    import jax

    jax.config.update("jax_platforms", "cpu")
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        audit_config,
    )

    artifacts: dict = {}
    rep = audit_config(path, shrink=shrink, replication_slack=slack,
                       artifacts_out=artifacts)
    out = {"path": path, "report": rep.to_dict(), "text": rep.format(),
           "failed_warn": rep.failed("warn"),
           "failed_error": rep.failed("error")}
    if contracts and artifacts:
        # the graph-contract ratchet rides the SAME lowering the absolute
        # rules just audited — no second compile per config.  A failure
        # here (corrupt snapshot, fingerprint bug) must become THIS
        # config's finding, not kill the whole sweep.
        try:
            from neuronx_distributed_training_tpu.analysis import (
                graph_contract as gc,
            )

            fp = gc.fingerprint_artifacts(
                artifacts["ctx"], artifacts["compiled"],
                artifacts["stablehlo"], config_name=os.path.basename(path))
            fp["shrunk"] = bool(shrink)
            crep = gc.check_contract(path, fp)
            out["contract"] = crep.to_dict()
            out["contract_text"] = (
                f"contract [{os.path.basename(path)}]: "
                f"{crep.worst() or 'clean'}"
                + ("\n" + crep.format() if crep.findings else ""))
            out["failed_warn"] |= crep.failed("warn")
            out["failed_error"] |= crep.failed("error")
        except Exception as e:  # noqa: BLE001 — a worker must return, not die
            out["contract"] = {"verdict": "error",
                               "error": f"{type(e).__name__}: {e}"}
            out["contract_text"] = (
                f"contract [{os.path.basename(path)}]: ERROR "
                f"({type(e).__name__}: {e})")
            out["failed_warn"] = out["failed_error"] = True
    elif contracts:
        out["contract"] = {"verdict": "error",
                           "skipped": "no artifacts (config failed earlier)"}
        out["contract_text"] = f"contract [{os.path.basename(path)}]: " \
                               f"skipped (audit failed before lowering)"
        out["failed_error"] = True
    return out


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", action="append", default=[],
                    help="YAML config to graph-audit (repeatable)")
    ap.add_argument("--all-examples", action="store_true",
                    help="graph-audit every examples/conf/*.yaml")
    ap.add_argument("--lint", action="store_true",
                    help="run the jaxlint source pass with the ratchet "
                         "baseline")
    ap.add_argument("--contracts", action="store_true",
                    help="also check each config's compiled fingerprint "
                         "against its committed graph contract "
                         "(analysis/contracts/ — reuses the audit's "
                         "lowering; tools/graph_contract.py is the "
                         "standalone ratchet CLI)")
    ap.add_argument("--fail-on", choices=["warn", "error"], default="error",
                    help="severity that fails the run (default: error)")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="graph-audit N configs in parallel processes (the "
                         "sweep is embarrassingly parallel); output order "
                         "stays deterministic (default 1: serial)")
    ap.add_argument("--no-shrink", dest="shrink", action="store_false",
                    help="audit configs at true size (needs a device world "
                         "as large as the config's parallel degrees)")
    ap.add_argument("--replication-slack", type=float, default=8.0,
                    help="GA201 fires above slack x the analytic per-device "
                         "budget (default 8)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full machine-readable report here "
                         "('-' for stdout)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the jaxlint ratchet baseline from the "
                         "current findings (review the diff!)")
    args = ap.parse_args()

    configs = list(args.config)
    if args.all_examples:
        import glob

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        configs += sorted(glob.glob(os.path.join(here, "examples/conf/*.yaml")))
    if not configs and not args.lint:
        ap.error("nothing to do: pass --config/--all-examples and/or --lint")
    if args.update_baseline and not args.lint:
        ap.error("--update-baseline only makes sense with --lint (the "
                 "baseline is regenerated from the lint findings)")

    # A static CPU analysis, pinned to CPU: neither this process nor a
    # --jobs worker may take a chip.  Size the virtual device world BEFORE
    # jax initializes its backend (the env crosses the spawn).
    if configs:
        world = max(_required_world(configs, args.shrink), 8)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={world}"
            ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    failed = False
    out: dict = {"reports": []}

    work = [(p, args.shrink, args.replication_slack, args.contracts)
            for p in configs]
    if args.jobs > 1 and len(work) > 1:
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor

        with ProcessPoolExecutor(
                max_workers=min(args.jobs, len(work)),
                mp_context=mp.get_context("spawn")) as pool:
            results = list(pool.map(_audit_worker, work))
    else:
        results = [_audit_worker(w) for w in work]

    for res in results:  # input order: deterministic merged output
        print(res["text"])
        if "contract_text" in res:
            print(res["contract_text"])
        print()
        report = res["report"]
        if "contract" in res:
            report = {**report, "contract": res["contract"]}
        out["reports"].append(report)
        failed |= res["failed_warn" if args.fail_on == "warn"
                      else "failed_error"]

    import jax

    jax.config.update("jax_platforms", "cpu")

    from neuronx_distributed_training_tpu.analysis import jaxlint

    if args.lint:
        full = jaxlint.lint_package()
        if args.update_baseline:
            jaxlint.write_baseline(full)
            print(f"jaxlint: baseline rewritten with {len(full.findings)} "
                  f"finding(s) -> {jaxlint.BASELINE_PATH}")
        fresh, stale = jaxlint.apply_ratchet(full, jaxlint.load_baseline())
        n_base = fresh.stats.get("baselined", 0)
        if not fresh.findings and not stale:
            print(f"jaxlint: clean ({n_base} baselined, 0 new)")
        else:
            print(fresh.format())
            for entry in stale:
                print(f"[ERROR] JL999: stale baseline entry (the finding it "
                      f"grandfathers no longer exists): {entry}")
                print("        fix: remove it from jaxlint_baseline.json "
                      "(or run --update-baseline) — the ratchet only "
                      "shrinks")
            if not args.update_baseline:
                failed = True
        out["jaxlint"] = {
            "new": [f.to_dict() for f in fresh.findings],
            "baselined": n_base,
            "stale_baseline_entries": stale,
        }

    if args.json:
        # shared writer (tools/_jsonout.py): with --json -, the payload is
        # guaranteed to be the single parseable LAST stdout line even when
        # warnings/log lines were emitted along the way
        from _jsonout import write_json

        write_json(out, args.json)

    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
