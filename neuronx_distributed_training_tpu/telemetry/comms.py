"""Interconnect observatory: measured collective bandwidth.

Every other roofline term is measured — overlap (telemetry.trace),
HBM (telemetry.memory), bubbles (step timeline) — but the comms term itself
was priced purely from the static ``ici_bandwidth_bytes`` tables in
``autotune/topology.py``.  This module closes that gap in three layers
(docs/observability.md "Interconnect observatory"):

- **In-loop achieved bandwidth** — :func:`comms_section` joins the
  per-collective-class wire seconds the trace analytics already extract
  (``trace_summary.json``'s ``overlap_by_class``) with the per-class byte
  volumes the planner already computes
  (``autotune.cost_model.collective_byte_volumes``) into
  ``comms/<class>/achieved_gbps`` + ``comms/<class>/efficiency`` (vs the
  topology table's peak).  The join is pure host arithmetic over two
  artifacts the run produces anyway — no new syncs, no graph changes.

- **Standalone microbenchmark** — :func:`run_comms_sweep` drives
  {all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all}
  x mesh axis x message size through the real mesh machinery
  (``parallel.mesh`` + ``parallel.sharding.shard_map``), with warmup +
  timed reps, and :func:`build_comms_summary` fits per-axis bandwidth +
  latency out of the sweep (the measured analog of the topology table)
  plus per-device timing skew that names a degraded link/host as a finding.
  ``tools/comms_bench.py`` is the CLI.

- **Close the loop** — ``comms_summary.json`` (:func:`write_comms_summary`,
  byte-stable) is content-sniffed by ``plan.py --calibrate-from``
  (:func:`is_comms_summary`) and turned into measured/prior per-axis
  bandwidth ratios by ``autotune.cost_model.comms_calibration_from_summary``
  so ``estimate_plan`` prices comms from what the wire actually delivered.

Bus-bandwidth conventions (the NCCL-tests vocabulary): for a logical
payload of ``B`` bytes over ``n`` ranks, a ring all-gather/reduce-scatter
moves ``B(n-1)/n`` per rank, an all-reduce twice that, a point-to-point
permute exactly ``B``, and an all-to-all ``B(n-1)/n`` — the same factors
``autotune.cost_model._ring_seconds`` prices, so measured and predicted
bandwidth are directly comparable.  ``achieved_gbps`` is always BUS
bandwidth (bus bytes / wire seconds), never algorithm bandwidth.

Stdlib-only at import time (like ``telemetry.fleet``) so the offline tools
can load it without jax; the sweep runner imports jax lazily.
"""

from __future__ import annotations

import json
import logging
import os
import statistics
import time
from pathlib import Path
from typing import Any, Mapping, Optional, Sequence

logger = logging.getLogger(__name__)

#: summary filename (next to run_summary.json / trace_summary.json)
COMMS_SUMMARY_NAME = "comms_summary.json"

COMMS_SUMMARY_SCHEMA = 1

#: collective-class vocabulary — must match utils.debug.COLLECTIVE_KINDS
#: (asserted by tests/test_comms.py; duplicated here so this module stays
#: importable without jax)
COMMS_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

#: cost-model axis name <-> mesh axis name (parallel.mesh.AXES)
AXIS_TO_MESH = {"tp": "model", "dp": "data", "pp": "pipe",
                "cp": "context", "ep": "expert"}
MESH_TO_AXIS = {v: k for k, v in AXIS_TO_MESH.items()}

#: a device whose timing probe runs this much slower than the fleet median
#: is named a degraded-link/host finding
SKEW_REL_THRESHOLD = 1.5


# --------------------------------------------------------------------------
# bus-bandwidth conventions
# --------------------------------------------------------------------------


def bus_bytes(kind: str, payload_bytes: float, n: int) -> float:
    """Bytes actually traversing the wire per rank for a logical payload of
    ``payload_bytes`` over ``n`` ranks (ring algorithm factors — the same
    ones ``cost_model._ring_seconds`` prices)."""
    if n <= 1 or payload_bytes <= 0:
        return 0.0
    b = float(payload_bytes)
    if kind == "all-reduce":
        return 2.0 * b * (n - 1) / n
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return b * (n - 1) / n
    if kind == "collective-permute":
        return b
    raise ValueError(f"unknown collective kind {kind!r}; expected one of "
                     f"{COMMS_KINDS}")


def ring_hops(kind: str, n: int) -> int:
    """Latency hops a ring algorithm pays for one collective over ``n``
    ranks — the per-point intercept weight the per-axis fit uses."""
    if n <= 1:
        return 0
    if kind == "all-reduce":
        return 2 * (n - 1)
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return n - 1
    if kind == "collective-permute":
        return 1
    raise ValueError(f"unknown collective kind {kind!r}; expected one of "
                     f"{COMMS_KINDS}")


# --------------------------------------------------------------------------
# layer 1: the in-loop achieved-bandwidth join
# --------------------------------------------------------------------------


def class_bus_bytes_per_step(byte_volumes: Mapping[str, Mapping[str, float]],
                             axis_sizes: Mapping[str, int]
                             ) -> dict[str, float]:
    """Per-collective-class BUS bytes per step: the planner's logical
    per-axis volumes (``collective_byte_volumes``) folded through the ring
    factors, summed over axes.  Axes with unknown/degenerate degree
    contribute nothing."""
    out: dict[str, float] = {}
    for axis, kinds in (byte_volumes or {}).items():
        try:
            n = int((axis_sizes or {}).get(axis, 0))
        except (TypeError, ValueError):
            continue
        if n <= 1 or not isinstance(kinds, Mapping):
            continue
        for kind, vol in kinds.items():
            try:
                bb = bus_bytes(str(kind), float(vol), n)
            except (TypeError, ValueError):
                continue
            if bb > 0:
                out[str(kind)] = out.get(str(kind), 0.0) + bb
    return out


def comms_section(facts: Mapping[str, Any],
                  overlap_by_class: Mapping[str, Any],
                  *, window_steps: int) -> Optional[dict]:
    """The ``comms`` section for ``trace_summary.json``/``run_summary.json``:
    measured wire seconds per class (trace analytics) joined with predicted
    bus bytes per class (cost model) into achieved Gb/s + efficiency vs the
    topology peak.

    ``facts`` is what the trainer arms via ``exp_manager.set_comms_facts``:
    ``byte_volumes`` (``collective_byte_volumes`` output), ``axis_sizes``
    (cost-model axis -> mesh degree), ``peak_bandwidth_bytes`` (the
    topology table's ICI prior), ``topology`` (its name).  Returns None
    when the join has nothing to say (no collectives traced, or no byte
    volumes) — observability never invents numbers.
    """
    if not facts or window_steps < 1:
        return None
    per_class = class_bus_bytes_per_step(
        facts.get("byte_volumes") or {}, facts.get("axis_sizes") or {})
    if not per_class:
        return None
    peak = float(facts.get("peak_bandwidth_bytes") or 0.0)
    classes: dict[str, dict] = {}
    for kind, bytes_step in sorted(per_class.items()):
        c = (overlap_by_class or {}).get(kind)
        if not isinstance(c, Mapping):
            continue
        try:
            wire = float(c.get("wire_seconds") or 0.0)
        except (TypeError, ValueError):
            continue
        if wire <= 0:
            continue
        wire_step = wire / float(window_steps)
        achieved_bps = bytes_step / wire_step
        entry = {
            "bus_bytes_per_step": round(bytes_step, 1),
            "wire_seconds_per_step": round(wire_step, 9),
            "achieved_gbps": round(achieved_bps / 1e9, 6),
            "count": int(c.get("count") or 0),
        }
        if peak > 0:
            entry["efficiency"] = round(achieved_bps / peak, 6)
        classes[kind] = entry
    if not classes:
        return None
    out: dict[str, Any] = {
        "classes": classes,
        "window_steps": int(window_steps),
    }
    if peak > 0:
        out["peak_bandwidth_gbps"] = round(peak / 1e9, 6)
    if facts.get("topology"):
        out["topology"] = str(facts["topology"])
    return out


def comms_metrics(section: Optional[Mapping[str, Any]]
                  ) -> dict[str, float]:
    """Flatten a ``comms`` section into the scalar metrics that ride the
    logging boundary (every sink + fleet beacons):
    ``comms/<class>/achieved_gbps`` and ``comms/<class>/efficiency``."""
    out: dict[str, float] = {}
    if not section:
        return out
    for kind, entry in (section.get("classes") or {}).items():
        if not isinstance(entry, Mapping):
            continue
        for field in ("achieved_gbps", "efficiency"):
            v = entry.get(field)
            if v is not None:
                try:
                    out[f"comms/{kind}/{field}"] = float(v)
                except (TypeError, ValueError):
                    continue
    return out


def degraded_link_alert_rule(kind: str = "all-gather", *, window: int = 3,
                             rel_drop: float = 0.5, action: str = "log"
                             ) -> dict:
    """The worked fleet-alert rule for interconnect degradation: achieved
    bandwidth for a collective class falling ``rel_drop`` below its own
    running peak (a flapping ICI link, a host on a degraded DCN path).
    Drop-in block for ``exp_manager.telemetry.alerts``; validated by
    ``telemetry.alerts.AlertRule.from_config`` like any other rule."""
    return {
        "metric": f"comms/{kind}/achieved_gbps",
        "window": int(window),
        "rel_drop": float(rel_drop),
        "action": str(action),
        "name": "comms_degraded_link",
    }


# --------------------------------------------------------------------------
# layer 2: the microbenchmark sweep + per-axis fit
# --------------------------------------------------------------------------


def fit_axis_bandwidth(points: Sequence[Mapping[str, float]]
                       ) -> Optional[dict]:
    """Least-squares fit of ``t = bus_bytes / bandwidth + hops * latency``
    over a sweep's (bus_bytes, hops, seconds) points — the measured analog
    of one topology-table row.

    Two-parameter linear fit via the normal equations (stdlib only).  When
    the system is degenerate (one message size, collinear points) or the
    fitted slope is non-positive (timing noise), falls back to the aggregate
    bus bandwidth ``sum(bytes)/sum(seconds)`` with zero latency — a fit
    never returns a negative or infinite bandwidth.  None when no usable
    points.
    """
    xs, hs, ys = [], [], []
    for p in points or ():
        try:
            x = float(p["bus_bytes"])
            h = float(p.get("hops", 0.0))
            y = float(p["seconds"])
        except (KeyError, TypeError, ValueError):
            continue
        if x > 0 and y > 0:
            xs.append(x)
            hs.append(h)
            ys.append(y)
    if not xs:
        return None
    sxx = sum(x * x for x in xs)
    shh = sum(h * h for h in hs)
    sxh = sum(x * h for x, h in zip(xs, hs))
    sxy = sum(x * y for x, y in zip(xs, ys))
    shy = sum(h * y for h, y in zip(hs, ys))
    det = sxx * shh - sxh * sxh
    slope = intercept = None
    if det > 0 and sxx > 0 and shh > 0:
        s = (sxy * shh - shy * sxh) / det
        l = (shy * sxx - sxy * sxh) / det
        if s > 0 and l >= 0:
            slope, intercept = s, l
    if slope is None and sxx > 0:
        s = sxy / sxx  # latency-free slope-only fit
        if s > 0:
            slope, intercept = s, 0.0
    if slope is None:
        slope = sum(ys) / sum(xs)  # aggregate bus bandwidth
        intercept = 0.0
    return {
        "bandwidth_bytes_per_s": round(1.0 / slope, 1),
        "latency_seconds": round(float(intercept), 9),
        "n_points": len(xs),
    }


def skew_findings(per_device: Mapping[str, float], *,
                  rel_threshold: float = SKEW_REL_THRESHOLD) -> list[dict]:
    """Degraded-link/host findings out of per-device probe timings: any
    device whose time exceeds ``rel_threshold`` x the fleet median is named
    (SPMD collectives run at the slowest participant's pace, so one slow
    device IS a degraded interconnect as far as the step time is
    concerned).  Pure function — the seeded-slow-device test feeds it
    directly."""
    vals = {}
    for dev, t in (per_device or {}).items():
        try:
            f = float(t)
        except (TypeError, ValueError):
            continue
        if f > 0:
            vals[str(dev)] = f
    if len(vals) < 2:
        return []
    med = statistics.median(vals.values())
    if med <= 0:
        return []
    out = []
    for dev in sorted(vals, key=lambda d: -vals[d]):
        ratio = vals[dev] / med
        if ratio > rel_threshold:
            out.append({
                "kind": "degraded_link",
                "device": dev,
                "seconds": round(vals[dev], 9),
                "median_seconds": round(med, 9),
                "ratio": round(ratio, 3),
                "message": (
                    f"device {dev} timing probe ran {ratio:.2f}x the fleet "
                    f"median ({vals[dev]:.6g}s vs {med:.6g}s; threshold "
                    f"{rel_threshold:g}x) — degraded link or host; SPMD "
                    f"collectives run at its pace"),
            })
    return out


def measure_device_skew(devices: Optional[Sequence[Any]] = None, *,
                        reps: int = 3, payload_bytes: int = 1 << 16
                        ) -> dict[str, float]:
    """Per-device timing probe (host->device transfer + a trivial op,
    blocked): median seconds per device, keyed by device id.  The relative
    spread — not the absolute number — is the signal: a degraded host/link
    shows up as one device far off the fleet median
    (:func:`skew_findings`)."""
    import jax
    import numpy as np

    devs = list(devices) if devices is not None else list(jax.devices())
    arr = np.zeros(max(int(payload_bytes) // 4, 1), dtype=np.float32)
    out: dict[str, float] = {}
    for d in devs:
        times = []
        for rep in range(max(int(reps), 1) + 1):
            t0 = time.perf_counter()
            x = jax.device_put(arr, d)
            (x + 1.0).block_until_ready()
            if rep > 0:  # rep 0 is warmup (compile + first transfer)
                times.append(time.perf_counter() - t0)
        out[str(d.id)] = statistics.median(times)
    return out


def _sweep_op(kind: str, mesh: Any, axis: str, payload_bytes: int):
    """Build (jitted_fn, placed_input, actual_payload_bytes) for one
    collective over one mesh axis.  Per-device logical payload is
    ``payload_bytes`` (shapes round down so tiny smoke sizes stay valid);
    the actual bytes are returned so the recorded rows never lie."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.parallel.sharding import shard_map

    n = int(mesh.shape[axis])
    spec = P(axis, None)

    if kind in ("all-reduce", "collective-permute"):
        elems = max(int(payload_bytes) // 4, 1)
        shape = (n, elems)  # per-device (1, elems) = the logical payload
        payload = elems * 4
    else:
        # AG shard / RS row / A2A chunk: per-device dim must split n ways
        elems = max(int(payload_bytes) // (4 * n), 1)
        shape = (n * n, elems) if kind in ("reduce-scatter", "all-to-all") \
            else (n, elems)
        payload = elems * 4 * n

    if kind == "all-reduce":
        def f(x):
            return lax.psum(x, axis)
        out_spec = spec
    elif kind == "all-gather":
        def f(x):
            return lax.all_gather(x, axis, axis=0, tiled=True)
        # no replication claim: each device keeps its gathered copy and the
        # out spec concatenates them — only the wire traffic matters here
        out_spec = spec
    elif kind == "reduce-scatter":
        def f(x):
            return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
        out_spec = spec
    elif kind == "collective-permute":
        perm = [(i, (i + 1) % n) for i in range(n)]

        def f(x):
            return lax.ppermute(x, axis, perm=perm)
        out_spec = spec
    elif kind == "all-to-all":
        def f(x):
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        out_spec = spec
    else:
        raise ValueError(f"unknown collective kind {kind!r}")

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=out_spec))
    x = jax.device_put(
        jnp.arange(shape[0] * shape[1], dtype=jnp.float32).reshape(shape),
        NamedSharding(mesh, spec))
    return fn, x, payload


def run_comms_sweep(mesh: Any, *,
                    sizes_bytes: Sequence[int] = (1 << 20, 4 << 20),
                    kinds: Optional[Sequence[str]] = None,
                    warmup: int = 1, reps: int = 3
                    ) -> dict[str, dict]:
    """Sweep collective kinds x mesh axes x message sizes on a live mesh.

    Per mesh axis with degree > 1 (named by its cost-model alias: model->tp,
    data->dp, pipe->pp, context->cp, expert->ep) runs each applicable
    collective class (``utils.debug.AXIS_COLLECTIVE_KINDS``) at each
    message size: ``warmup`` untimed reps (compile + first dispatch), then
    ``reps`` timed reps blocked individually.  Returns
    ``{axis: {mesh_axis, size, sweep: [rows...]}}`` ready for
    :func:`build_comms_summary`.  CPU-mesh testable: the virtual-device
    CPU backend executes the same collectives the TPU mesh would.
    """
    from neuronx_distributed_training_tpu.utils.debug import (
        AXIS_COLLECTIVE_KINDS,
    )

    results: dict[str, dict] = {}
    for mesh_axis, size in dict(mesh.shape).items():
        n = int(size)
        axis = MESH_TO_AXIS.get(str(mesh_axis))
        if n <= 1 or axis is None:
            continue
        axis_kinds = [k for k in AXIS_COLLECTIVE_KINDS.get(axis, ())
                      if kinds is None or k in kinds]
        rows = []
        for kind in axis_kinds:
            for size_bytes in sizes_bytes:
                try:
                    fn, x, payload = _sweep_op(kind, mesh, mesh_axis,
                                               int(size_bytes))
                    for _ in range(max(int(warmup), 1)):
                        fn(x).block_until_ready()
                    times = []
                    for _ in range(max(int(reps), 1)):
                        t0 = time.perf_counter()
                        fn(x).block_until_ready()
                        times.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — one op failing must
                    # not void the rest of the sweep (e.g. a backend without
                    # a given collective); the gap is visible in the rows
                    logger.warning("comms sweep %s over %s @ %d bytes "
                                   "failed: %s", kind, mesh_axis,
                                   size_bytes, e)
                    continue
                bb = bus_bytes(kind, payload, n)
                t_med = statistics.median(times)
                rows.append({
                    "collective": kind,
                    "payload_bytes": int(payload),
                    "bus_bytes": round(bb, 1),
                    "hops": ring_hops(kind, n),
                    "seconds_median": round(t_med, 9),
                    "seconds_min": round(min(times), 9),
                    "reps": len(times),
                    "bus_gbps": round(bb / t_med / 1e9, 6),
                })
        if rows:
            results[axis] = {
                "mesh_axis": str(mesh_axis),
                "size": n,
                "sweep": rows,
            }
    return results


def build_comms_summary(axis_results: Mapping[str, Mapping[str, Any]], *,
                        topology_name: str,
                        prior_bandwidth_bytes: float,
                        prior_latency_seconds: float,
                        device_skew: Optional[Mapping[str, float]] = None,
                        skew_rel_threshold: float = SKEW_REL_THRESHOLD
                        ) -> dict:
    """Assemble the ``comms_summary.json`` document: per-axis sweep rows +
    fitted bandwidth/latency, measured/prior ratios against the topology
    table (recorded IN the summary so calibration is self-contained — the
    reader never has to guess which prior the bench saw), and per-device
    skew findings."""
    axes: dict[str, Any] = {}
    findings: list[dict] = []
    for axis in sorted(axis_results or {}):
        r = axis_results[axis]
        fit = fit_axis_bandwidth([
            {"bus_bytes": row["bus_bytes"], "hops": row.get("hops", 0),
             "seconds": row["seconds_median"]}
            for row in r.get("sweep") or ()
        ])
        entry: dict[str, Any] = {
            "mesh_axis": r.get("mesh_axis"),
            "size": int(r.get("size") or 0),
            "sweep": list(r.get("sweep") or ()),
        }
        if fit:
            entry["fit"] = fit
            if prior_bandwidth_bytes > 0:
                entry["bandwidth_ratio"] = round(
                    fit["bandwidth_bytes_per_s"] / prior_bandwidth_bytes, 6)
            if prior_latency_seconds > 0 and fit["latency_seconds"] > 0:
                entry["latency_ratio"] = round(
                    fit["latency_seconds"] / prior_latency_seconds, 6)
        axes[axis] = entry
    skew_block = None
    if device_skew:
        per_dev = {str(k): round(float(v), 9)
                   for k, v in device_skew.items()}
        findings = skew_findings(per_dev, rel_threshold=skew_rel_threshold)
        skew_block = {
            "per_device": per_dev,
            "median_seconds": round(
                statistics.median(per_dev.values()), 9) if per_dev else None,
            "rel_threshold": float(skew_rel_threshold),
            "findings": findings,
        }
    out: dict[str, Any] = {
        "schema": COMMS_SUMMARY_SCHEMA,
        "kind": "comms_summary",
        "topology": str(topology_name),
        "prior": {
            "ici_bandwidth_bytes": float(prior_bandwidth_bytes),
            "ici_latency_seconds": float(prior_latency_seconds),
        },
        "axes": axes,
        "findings": findings,
    }
    if skew_block is not None:
        out["device_skew"] = skew_block
    return out


# --------------------------------------------------------------------------
# layer 3: the artifact (sniff / load / write)
# --------------------------------------------------------------------------


def is_comms_summary(doc: Any) -> bool:
    """Content sniff for ``plan.py --calibrate-from`` (the comms analog of
    ``telemetry.memory.is_memory_summary``): the explicit ``kind`` marker,
    or the axes+prior pair no other summary carries."""
    if not isinstance(doc, Mapping):
        return False
    if doc.get("kind") == "comms_summary":
        return True
    return isinstance(doc.get("axes"), Mapping) \
        and isinstance(doc.get("prior"), Mapping)


def load_comms_summary(source: Any) -> dict:
    """Tolerant loader: a summary dict passes through; a file path is
    parsed; a run directory resolves ``comms_summary.json`` inside it.
    Raises ``ValueError`` (not FileNotFoundError tracebacks) on anything
    unusable — the planner turns that into a report error."""
    if isinstance(source, Mapping):
        return dict(source)
    path = Path(source)
    if path.is_dir():
        path = path / COMMS_SUMMARY_NAME
    if not path.is_file():
        raise ValueError(f"no comms summary at {path}")
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise ValueError(f"unreadable comms summary {path}: {e}")
    if not isinstance(doc, Mapping):
        raise ValueError(f"comms summary {path} is not a JSON object")
    return dict(doc)


def write_comms_summary(summary: Mapping[str, Any],
                        path: str | Path) -> None:
    """Byte-stable atomic write (sorted keys, indent 1, trailing newline —
    the same serialize-first + temp/rename contract as
    ``fleet.write_fleet_summary``): identical content always produces
    identical bytes, so committed fixtures diff cleanly."""
    data = json.dumps(summary, indent=1, sort_keys=True) + "\n"
    spath = str(path)
    tmp = f"{spath}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:  # pragma: no cover — some filesystems refuse
            pass
    os.replace(tmp, spath)


def bench_comms_facts(summary: Mapping[str, Any]) -> dict:
    """The ``comms`` block of ``tools/comms_bench.py``'s JSON line out of a
    comms summary: per-axis fitted bandwidth (+ measured/prior ratio) and
    per-class best achieved bus Gb/s across the sweep."""
    prior = float((summary.get("prior") or {}).get(
        "ici_bandwidth_bytes") or 0.0)
    axes: dict[str, Any] = {}
    classes: dict[str, Any] = {}
    for axis, entry in sorted((summary.get("axes") or {}).items()):
        if not isinstance(entry, Mapping):
            continue
        fit = entry.get("fit")
        if isinstance(fit, Mapping) and fit.get("bandwidth_bytes_per_s"):
            rec = {
                "bandwidth_gbps": round(
                    float(fit["bandwidth_bytes_per_s"]) / 1e9, 6),
                "latency_us": round(
                    float(fit.get("latency_seconds") or 0.0) * 1e6, 3),
            }
            if entry.get("bandwidth_ratio") is not None:
                rec["bandwidth_ratio"] = float(entry["bandwidth_ratio"])
            axes[axis] = rec
        for row in entry.get("sweep") or ():
            if not isinstance(row, Mapping):
                continue
            kind = str(row.get("collective") or "")
            try:
                gbps = float(row.get("bus_gbps") or 0.0)
            except (TypeError, ValueError):
                continue
            if kind and gbps > 0:
                cur = classes.setdefault(kind, {"achieved_gbps": 0.0})
                cur["achieved_gbps"] = round(
                    max(cur["achieved_gbps"], gbps), 6)
    if prior > 0:
        for rec in classes.values():
            rec["efficiency"] = round(
                rec["achieved_gbps"] * 1e9 / prior, 6)
    out: dict[str, Any] = {}
    if classes:
        out["classes"] = classes
    if axes:
        out["axes"] = axes
    return out
