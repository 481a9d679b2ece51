#!/usr/bin/env python
"""Offline checkpoint-integrity verification of a run dir.

Walks every retained step of a checkpoint directory (or one ``--step``),
re-reads each digested item template-free, re-hashes, and compares against
the ``integrity`` sidecar saved with the step (docs/elasticity.md
"Integrity & walk-back").  Exit status: 0 when every step verifies (``ok``
or pre-integrity ``legacy``), 1 when any step is corrupt or nothing was
found to verify.

    python tools/ckpt_verify.py <run_dir|checkpoint_dir>
    python tools/ckpt_verify.py <dir> --step 40
    python tools/ckpt_verify.py <dir> --json -          # _jsonout contract
    python tools/ckpt_verify.py <dir> --quarantine      # apply the ledger

``--quarantine`` applies the same quarantine auto-resume would: corrupt
step dirs are renamed out of the discovery namespace and recorded in
``quarantine_ledger.json`` — the next resume walks straight to the newest
good step without re-verifying the corpse.  Without the flag the tool only
REPORTS (safe on a live run's directory).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
from pathlib import Path
from typing import Any, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # tools/_jsonout

logger = logging.getLogger("nxdt.ckpt_verify")


def resolve_checkpoint_dir(path: str | Path) -> Optional[Path]:
    """Accept a run dir (``<...>/version_N``), an experiment base dir, or a
    checkpoint dir directly — the same layout ``ExpManager`` writes."""
    p = Path(path)
    if not p.is_dir():  # missing, or an operator slip like .../metrics.jsonl
        return None
    if (p / "checkpoints").is_dir():
        return p / "checkpoints"
    if p.name == "checkpoints" or any(c.name.isdigit() for c in p.iterdir()
                                      if c.is_dir()):
        return p
    # experiment base dir: newest version_N (same parse as ExpManager)
    from neuronx_distributed_training_tpu.trainer.exp_manager import (
        latest_version,
    )

    v = latest_version(p)
    if v is not None and (p / f"version_{v}" / "checkpoints").is_dir():
        return p / f"version_{v}" / "checkpoints"
    return None


def verify_dir(ck_dir: Path, *, step: Optional[int] = None,
               quarantine: bool = False) -> dict[str, Any]:
    """Verify all retained steps (or one) under ``ck_dir``; returns the
    report payload (the CLI's JSON)."""
    from neuronx_distributed_training_tpu.checkpoint import integrity as I

    mgr = I.open_readonly_manager(ck_dir)
    quarantined: list[int] = []
    verdicts = []
    try:
        steps = sorted(mgr.all_steps() or [])
        if step is not None:
            if int(step) not in steps:
                return {"ok": False, "checkpoint_dir": str(ck_dir),
                        "error": f"step {step} not found (retained: {steps})"}
            steps = [int(step)]
        for s in steps:
            v = I.verify_step(ck_dir, s, mgr=mgr)
            verdicts.append(v)
            tag = {"ok": "OK", "legacy": "LEGACY (no sidecar — unverified)",
                   "corrupt": "CORRUPT", "gone": "GONE"}[v.status]
            print(f"step {s:>8}: {tag}  "
                  f"({v.groups_checked} group(s), {v.seconds:.2f}s)")
            for f in v.failures:
                print(f"             - {f}")
            if v.status == "corrupt" and quarantine:
                I.apply_quarantine(ck_dir, s, reason=v.failures[0]
                                   if v.failures else "digest-mismatch",
                                   failures=v.failures)
                quarantined.append(s)
        if quarantined:
            mgr.reload()
    finally:
        try:
            mgr.close()
        except Exception:  # noqa: BLE001 — read-only teardown
            pass
    ledger = I.read_ledger(ck_dir)
    corrupt = [v for v in verdicts if v.status == "corrupt"]
    return {
        "ok": bool(verdicts) and not corrupt,
        "checkpoint_dir": str(ck_dir),
        "steps": [v.to_dict() for v in verdicts],
        "corrupt_steps": [v.step for v in corrupt],
        "legacy_steps": [v.step for v in verdicts if v.status == "legacy"],
        "quarantined": quarantined,
        "ledger_entries": len(ledger),
    }


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="run dir, experiment base dir, or "
                                 "checkpoint dir")
    ap.add_argument("--step", type=int, default=None,
                    help="verify only this retained step")
    ap.add_argument("--quarantine", action="store_true",
                    help="rename corrupt steps out of discovery + write the "
                         "quarantine ledger (default: report only)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the report as JSON ('-' = stdout, last "
                         "line, tools/_jsonout contract)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    # verification is a host-side read: stay off any TPU the box may have
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=1").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    ck_dir = resolve_checkpoint_dir(args.path)
    if ck_dir is None:
        logger.error("no checkpoint directory under %s", args.path)
        report: dict[str, Any] = {
            "ok": False, "error": f"no checkpoint directory under {args.path}"}
    else:
        report = verify_dir(ck_dir, step=args.step,
                            quarantine=args.quarantine)
    if args.json:
        from _jsonout import write_json

        write_json(report, args.json)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
