"""Per-layer metrics of a traced run: each ``layer_metrics/<name>.json``
names a reader under ``benchmark/readers`` and its arguments."""

from __future__ import annotations

import importlib
from typing import Any

from benchmark import trace_reduce
from benchmark.harness import say


def read_all(ctx: dict, result: dict) -> dict:
    """Returns the keys a traced run adds to the result object."""
    xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
    ctx["trace"] = None
    if xplane is not None and ctx["on_chip"]:
        ctx["trace"] = trace_reduce.reduce(xplane)
        keep = ctx.get("keep_xplane")
        if keep:
            keep.parent.mkdir(parents=True, exist_ok=True)
            keep.write_bytes(xplane.read_bytes())
    metrics: dict[str, Any] = {}
    for spec in ctx["cell"].per_layer:
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **(spec.get("args") or {}))
        if value is None:
            say(f"layer metric {spec['name']}: nothing to read, left out")
            continue
        metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    out: dict[str, Any] = {"metrics": metrics}
    tr = ctx["trace"]
    if tr is not None:
        say(f"trace: {tr['steps']} step periods on {tr['chips']} chip(s), window "
            f"{tr['window_s']:.4f}s, busy {tr['busy_s']:.4f}s")
        out["device"] = {**result["device"], "busy_s": tr["busy_s"],
                         "window_s": tr["window_s"]}
        top = sorted(tr["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                            "idle_gaps": tr["idle_gaps"]}
    return out
