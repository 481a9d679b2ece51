"""Find a cell's data files by the names in ``BENCHMARK.json``."""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
HEADER_KEYS = ("source", "reduced", "assumed", "deployment")


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict          # the configuration file: header + trainer_config
    traffic_name: str
    traffic: dict         # the traffic file
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list       # layer_metrics/<name>.json of the metrics it reports

    @property
    def model(self) -> dict:
        return self.config["trainer_config"]["model"]


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_config_file(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = next((c for c in bench["configs"] if c["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no configuration {name!r}")
    cfg = _json(root / entry["file"])
    missing = [k for k in HEADER_KEYS + ("trainer_config",) if k not in cfg]
    if missing:
        raise ValueError(f"{entry['file']}: header lacks {missing}")
    if len(str(cfg["source"])) > 200:
        raise ValueError(f"{entry['file']}: source is over 200 characters")
    if cfg["source"] != entry["source"]:
        raise ValueError(f"{entry['file']}: source differs from BENCHMARK.json")
    if sorted(cfg["reduced"]) != sorted(entry["reduced"]):
        raise ValueError(f"{entry['file']}: reduced differs from BENCHMARK.json")
    return cfg


def load_layer_metric(name: str, root: Path = ROOT) -> dict:
    spec = _json(root / "benchmark" / "layer_metrics" / f"{name}.json")
    if spec.get("name") != name:
        raise ValueError(f"layer_metrics/{name}.json names {spec.get('name')!r}")
    return spec


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        known = ", ".join(x["name"] for x in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")
    per_layer = []
    for m in bench["per_layer"]:
        if _applies(m, name):
            spec = load_layer_metric(m["name"], root)
            for k in ("unit", "layer", "moves", "better", "source"):
                if spec.get(k) != m[k]:
                    raise ValueError(
                        f"layer_metrics/{m['name']}.json: {k} differs from "
                        f"BENCHMARK.json")
            per_layer.append(spec)
    return Cell(
        name=name, chips=int(w["chips"]), why=w["why"],
        config_name=w["config"],
        config=load_config_file(bench, w["config"], root),
        traffic_name=w["traffic"],
        traffic=_json(root / "benchmark" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
    )
