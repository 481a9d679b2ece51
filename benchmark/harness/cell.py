"""Find a cell's data files, and its two modules, by the names in
``BENCHMARK.json`` and in the configuration's own file.

What a PR that adds a configuration brings, all of it new files and entries:

- an entry under ``configs`` and its cells under ``workloads`` of
  ``BENCHMARK.json``;
- ``benchmark/configs/<file>.json``: the header (``HEADER_KEYS``), the
  ``trainer_config`` as it is run, and optionally ``modules`` and ``widths``;
- ``benchmark/traffic/<traffic>.json`` for each new traffic mix;
- ``benchmark/limits/<configuration>.json``: the limits ``correct`` holds it
  to (``check.limits_for``; else its entry in ``benchmark/limits.json``, and a
  name in both is an error);
- ``benchmark/references/<stem>.py`` and ``benchmark/operations/<stem>.py``,
  where ``modules`` names them (stems by the rule ``NAME``, and identifiers).
  A configuration that names none gets ``DEFAULT_MODULES``.

The two modules' contract (``CONTRACT``; held by ``tests/benchmark`` over
every configuration):

- reference: ``init_params(model, key)`` the seeded weights under the
  trainer's leaf paths, ``leaf_names(tree)``, and ``run(model, optim, clip,
  tokens_per_step, seed, shard=None)`` returning ``loss`` (one per checked
  step) and per-leaf ``grad1`` and ``dparam``.  Float32, matmul precision
  ``highest``, importing nothing of the program.  Its control may hang on a
  further keyword (``quant=``) that the harness never passes;
- operations: ``train_flops_per_token(model, seq_len)`` returning at least
  ``total`` (its other keys are printed), and ``kernel_calls(model, traffic,
  data_parallel)`` returning, per kernel kind as the trace reduction names it,
  ``flops`` and ``bytes`` of one call and the ``calls`` a step makes on one
  chip.

``widths`` maps a key of ``trainer_config.model`` (dotted below it, as
``moe.top_k``) to the key of ``published`` it must equal; absent, it is
``DEFAULT_WIDTHS``.  ``header_faults`` holds a file to it: a declared map
covers what the default covers and every width besides, and only a count that
``reduced`` lists by the model's own key may differ from its published count.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
HEADER_KEYS = ("source", "published", "reduced", "assumed", "deployment")
#: what a configuration gets that names no module of its own
DEFAULT_MODULES = {"reference": "benchmark.reference",
                   "operations": "benchmark.flops"}
MODULE_DIRS = {"reference": "references", "operations": "operations"}
CONTRACT = {"reference": ("init_params", "leaf_names", "run"),
            "operations": ("train_flops_per_token", "kernel_calls")}
#: what ``reduced`` may never name, and a declared ``widths`` has to cover
WIDTH = re.compile(
    r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head_|top_k|per_tok")
#: but a key that counts layers is a depth whatever else its name holds: the
#: ``hidden`` in a source's ``num_hidden_layers`` is no width
DEPTH = re.compile(r"(^|[._])layers$")
#: the only keys of the model block that ``reduced`` may excuse from equalling
#: their published key: counts of what is held here (heads, experts, rows)
COUNT = re.compile(r"(^|\.)(num_\w+|vocab_size)$")
#: model key -> published key, for a file that declares no ``widths``; a
#: declared map covers each of these keys that the model block has
DEFAULT_WIDTHS = {
    **{k: k for k in ("hidden_size", "intermediate_size", "num_attention_heads",
                      "num_key_value_heads", "head_dim", "vocab_size", "rope_theta")},
    "moe.num_experts": "num_local_experts", "moe.top_k": "num_experts_per_tok"}


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
    reference: ModuleType     # the configuration's plain reference
    operations: ModuleType    # its count of required operations and kernel calls
    root: Path = ROOT     # the checkout the files were found in

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
    faults = header_faults(cfg, entry["reduced"])
    if faults:
        raise ValueError(f"{entry['file']}: " + "; ".join(faults))
    return cfg


def names_a_width(key: str) -> bool:
    """Whether ``reduced`` may not list ``key``: a width, and no depth."""
    return bool(WIDTH.search(key)) and not DEPTH.search(key)


def _flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def header_faults(cfg: dict, reduced: list) -> list:
    """What is wrong with a configuration file's widths, as text; empty if
    nothing.  No key of ``reduced`` is a width.  Every key of ``widths`` equals
    its published key, but a count (``COUNT``) that ``reduced`` lists by the
    model's own key (``vocab_size``, ``moe.num_experts``), for which the file
    states the published count and the deployment instead.  A declared
    map covers every numeric key of the model block that ``DEFAULT_WIDTHS``
    holds or ``names_a_width`` takes for one, so that a new configuration is
    held to no less than the accepted ones."""
    faults = [f"reduced names a width: {k}" for k in reduced if names_a_width(k)]
    model, published = _flat(cfg["trainer_config"]["model"]), cfg["published"]
    widths = cfg.get("widths")
    if widths is None:
        widths = {k: v for k, v in DEFAULT_WIDTHS.items()
                  if "moe" in cfg["trainer_config"]["model"] or not k.startswith("moe.")}
    else:
        faults += [f"widths leaves out the model's {k}" for k, v in model.items()
                   if (k in DEFAULT_WIDTHS or names_a_width(k)) and k not in widths
                   and isinstance(v, (int, float)) and not isinstance(v, bool)]
    for key, pub in widths.items():
        if pub not in published:
            faults.append(f"published lacks {pub} (for {key})")
        elif key in reduced:
            if not COUNT.search(key):
                faults.append(f"reduced cuts {key}, which is no count")
            if not cfg["deployment"]:
                faults.append(f"{key} is cut and no deployment is stated")
        elif model.get(key) != published[pub]:
            faults.append(f"{key} is {model.get(key)!r}, "
                          f"published {pub} is {published[pub]!r}")
    return faults


def _import(dotted: str, root: Path) -> ModuleType:
    """``benchmark.<...>`` of the checkout at ``root``, by path: executed once
    a process and kept in ``sys.modules`` (dataclasses and pickle look a module
    up there), the repository's own under its plain name, where ``import``
    finds it too, another checkout's under a name of its own."""
    name = dotted if root == ROOT else f"{dotted}@{root}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, root.joinpath(*dotted.split(".")).with_suffix(".py"))
        sys.modules[name] = module = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(module)  # FileNotFoundError names the path
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_modules(cfg: dict, root: Path = ROOT) -> dict:
    """The configuration's reference and operations modules, of the checkout
    at ``root``: the files its ``modules`` block names, else
    ``DEFAULT_MODULES``."""
    named = cfg.get("modules") or {}
    if set(named) - set(CONTRACT):
        raise ValueError(f"modules: unknown kind {sorted(set(named) - set(CONTRACT))}")
    found = {}
    for kind, needs in CONTRACT.items():
        stem = named.get(kind)
        if stem is not None and not (NAME.match(stem) and stem.isidentifier()):
            raise ValueError(f"modules.{kind}: {stem!r} is no module's name")
        module = _import(DEFAULT_MODULES[kind] if stem is None else
                         f"benchmark.{MODULE_DIRS[kind]}.{stem}", root)
        lacks = [f for f in needs if not callable(getattr(module, f, None))]
        if lacks:
            sys.modules.pop(module.__name__, None)   # not kept: the file may be mended
            raise AttributeError(
                f"{kind} module {module.__name__} ({module.__file__}) lacks {lacks}")
        found[kind] = module
    return found


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
    config = load_config_file(bench, w["config"], root)
    return Cell(
        name=name, chips=int(w["chips"]), why=w["why"],
        config_name=w["config"], config=config, root=root,
        **load_modules(config, root),
        traffic_name=w["traffic"],
        traffic=_json(root / "benchmark" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
    )
