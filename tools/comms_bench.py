#!/usr/bin/env python
"""Interconnect microbenchmark — measured collective bandwidth per mesh axis.

Sweeps {all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all} x mesh axis x message size through the repo's REAL mesh
machinery (``parallel.sharding.shard_map`` over a ``parallel.mesh`` mesh),
fits per-axis bandwidth + latency from the timed points (the same
bus-bandwidth conventions ``autotune.cost_model._ring_seconds`` prices
with), probes per-device timing skew, and writes a byte-stable
``comms_summary.json`` — the measured interconnect the planner can
calibrate against (``tools/plan.py --calibrate-from``).  It measures and
calibrates; it judges nothing (a time is judged by the benchmark's ledger).

    python tools/comms_bench.py --smoke --json -
    python tools/comms_bench.py --devices 8 --tp 2 --pp 2 --out run_dir
    python tools/comms_bench.py --sizes 1048576,4194304 --reps 5
    python tools/plan.py --config cfg.yaml --calibrate-from comms_summary.json

A device whose timing sits beyond ``--skew-threshold`` x the median lands
in the summary's ``findings`` as a ``degraded_link`` — and
``telemetry.comms.degraded_link_alert_rule()`` is the worked in-loop alert
rule for the same signal (docs/observability.md 'Interconnect
observatory').  ``--json`` writes through the shared ``tools/_jsonout.py``
writer: with ``--json -`` the LAST stdout line is guaranteed parseable
JSON (one line: ``metric=comms_bench_sweep`` + the per-axis fits).
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # tools/_jsonout


def _fmt(v, nd=3) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        a = abs(v)
        if a != 0 and (a >= 1e6 or a < 1e-3):
            return f"{v:.3e}"
        return f"{v:.{nd}f}"
    return str(v)


def render(summary: dict) -> str:
    """Human rendering of a comms summary (the full table lives in
    tools/comms_report.py — this is the sweep-side echo)."""
    prior = dict(summary.get("prior") or {})
    lines = [f"interconnect sweep — topology={summary.get('topology')} "
             f"prior={float(prior.get('ici_bandwidth_bytes') or 0) / 1e9:g}"
             f" GB/s"]
    for axis, entry in sorted((summary.get("axes") or {}).items()):
        fit = entry.get("fit") or {}
        head = (f"  {axis} (mesh axis {entry.get('mesh_axis')}, "
                f"size {entry.get('size')}):")
        if fit.get("bandwidth_bytes_per_s"):
            bw = float(fit["bandwidth_bytes_per_s"]) / 1e9
            lat = float(fit.get("latency_seconds") or 0) * 1e6
            head += f"  bw={bw:.3f} GB/s  lat={lat:.1f}us"
            if entry.get("bandwidth_ratio") is not None:
                head += f"  measured/prior={entry['bandwidth_ratio']:.2f}"
        lines.append(head)
        for row in entry.get("sweep") or ():
            lines.append(
                f"    {row['collective']:<18s} payload="
                f"{int(row['payload_bytes']):>9d}B  bus="
                f"{_fmt(row.get('bus_gbps'))} GB/s  t="
                f"{_fmt(row.get('seconds_median'), 6)}s")
    for f in summary.get("findings") or ():
        lines.append(f"  FINDING [{f.get('kind')}]: {f.get('message')}")
    if not summary.get("findings"):
        lines.append("  no degraded-link findings")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"],
                    help="jax platform (default cpu: the sweep is testable "
                         "on virtual host devices)")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU device count (cpu platform only; "
                         "default 8 — tp=2 x pp=2 x dp=2)")
    ap.add_argument("--tp", type=int, default=2,
                    help="tensor-parallel degree of the sweep mesh")
    ap.add_argument("--pp", type=int, default=2,
                    help="pipeline-parallel degree of the sweep mesh")
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree of the sweep mesh")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel degree of the sweep mesh")
    ap.add_argument("--sizes", default="1048576,4194304",
                    help="comma-separated payload sizes in bytes "
                         "(default 1MiB,4MiB)")
    ap.add_argument("--kinds", default=None,
                    help="comma-separated collective kinds to sweep "
                         "(default: every kind the axis carries, per "
                         "utils.debug.AXIS_COLLECTIVE_KINDS)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions per point (median wins)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed warmup calls per point (compile)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI shape: 64K/256K payloads, 2 reps")
    ap.add_argument("--no-skew", dest="skew", action="store_false",
                    help="skip the per-device timing-skew probe")
    ap.add_argument("--skew-threshold", type=float, default=None,
                    help="flag a device beyond this multiple of the median "
                         "probe time as a degraded link (default "
                         "telemetry.comms.SKEW_REL_THRESHOLD)")
    ap.add_argument("--out", default="comms_summary.json", metavar="PATH",
                    help="where to write comms_summary.json (a directory "
                         "gets the canonical file name; default ./"
                         "comms_summary.json)")
    ap.add_argument("--json", metavar="PATH",
                    help="one JSON line ('-' = stdout last line, "
                         "the shared tools/_jsonout contract)")
    args = ap.parse_args(argv)

    # size the virtual CPU world BEFORE jax initializes
    if args.platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from neuronx_distributed_training_tpu.autotune.topology import (
        resolve_topology,
    )
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )
    from neuronx_distributed_training_tpu.telemetry import comms

    devices = jax.devices()
    try:
        mesh = build_mesh(MeshConfig(
            tensor_model_parallel_size=args.tp,
            pipeline_model_parallel_size=args.pp,
            context_parallel_size=args.cp,
            expert_model_parallel_size=args.ep,
        ), devices)
    except (ValueError, AssertionError) as e:
        print(f"comms_bench: mesh build failed for {len(devices)} devices: "
              f"{e}", file=sys.stderr)
        if args.json:
            from _jsonout import write_json

            write_json({"ok": False, "metric": "comms_bench_sweep",
                        "error": str(e)}, args.json)
        return 2

    if args.smoke:
        sizes = (1 << 16, 1 << 18)
        reps, warmup = 2, 1
    else:
        sizes = tuple(int(s) for s in args.sizes.split(",") if s)
        reps, warmup = args.reps, args.warmup
    kinds = tuple(k.strip() for k in args.kinds.split(",") if k.strip()) \
        if args.kinds else None

    axis_results = comms.run_comms_sweep(
        mesh, sizes_bytes=sizes, kinds=kinds, warmup=warmup, reps=reps)
    topo = resolve_topology(device=devices[0])
    skew = comms.measure_device_skew(devices) if args.skew else None
    summary = comms.build_comms_summary(
        axis_results, topology_name=topo.name,
        prior_bandwidth_bytes=topo.ici_bandwidth_bytes,
        prior_latency_seconds=topo.ici_latency_seconds,
        device_skew=skew,
        skew_rel_threshold=(args.skew_threshold
                            if args.skew_threshold is not None
                            else comms.SKEW_REL_THRESHOLD))

    out = Path(args.out)
    if out.is_dir() or args.out.endswith(os.sep):
        out = out / comms.COMMS_SUMMARY_NAME
    comms.write_comms_summary(summary, out)

    print(render(summary))
    print(f"wrote {out}")

    facts_block = comms.bench_comms_facts(summary)
    ratios = [a.get("bandwidth_ratio")
              for a in (facts_block.get("axes") or {}).values()
              if a.get("bandwidth_ratio") is not None]
    payload = {
        "metric": "comms_bench_sweep",
        "value": round(min(ratios), 6) if ratios else 0.0,
        "unit": "min_axis_bandwidth_measured_over_prior",
        "device": getattr(devices[0], "device_kind", devices[0].platform),
        "mesh": {k: int(v) for k, v in dict(mesh.shape).items() if v > 1},
        "sizes_bytes": list(sizes),
        "comms": facts_block,
        "findings": summary.get("findings") or [],
        "comms_summary_path": str(out),
        "note": ("bus-bandwidth conventions (all-reduce 2B(n-1)/n, "
                 "AG/RS/A2A B(n-1)/n, permute B) — the same factors the "
                 "cost model's _ring_seconds prices with"),
    }
    if args.json:
        from _jsonout import write_json

        write_json(payload, args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
