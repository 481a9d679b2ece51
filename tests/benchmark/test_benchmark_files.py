"""The benchmark is data: every file is found by a name in BENCHMARK.json,
and BENCHMARK.json keeps to the contract's limits."""

import json
import re

import pytest

from benchmark.harness import cell as cells
from benchmark.harness.cell import HERE, NAME, ROOT

BENCH = cells.load_benchmark()
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def stems(sub):
    if not (HERE / sub).is_dir():
        return set()
    return {p.stem for p in (HERE / sub).iterdir() if p.is_file()}


CONFIG_FILES = {c["name"]: cells.load_config_file(BENCH, c["name"])
                for c in BENCH["configs"]}


def test_every_data_file_is_referenced():
    assert {(ROOT / c["file"]).stem for c in BENCH["configs"]} == stems("configs")
    assert {w["traffic"] for w in BENCH["workloads"]} == stems("traffic")
    assert {m["name"] for m in BENCH["per_layer"]} == stems("layer_metrics")
    assert {w["config"] for w in BENCH["workloads"]} == {c["name"] for c in BENCH["configs"]}
    readers = {cells.load_layer_metric(m["name"])["reader"] for m in BENCH["per_layer"]}
    assert readers == stems("readers") - {"__init__"}
    # what a configuration brings of its own is named by it, and by nothing else
    named = [cfg.get("modules") or {} for cfg in CONFIG_FILES.values()]
    assert {m["reference"] for m in named if "reference" in m} == stems("references")
    assert {m["operations"] for m in named if "operations" in m} == stems("operations")
    assert stems("limits") <= set(CONFIG_FILES)


def test_a_stray_file_fails_loudly(tmp_path):
    (tmp_path / "benchmark" / "layer_metrics").mkdir(parents=True)
    with pytest.raises(FileNotFoundError):
        cells.load_layer_metric("not_there", tmp_path)
    with pytest.raises(KeyError, match="no workload"):
        cells.load_cell("not-a-cell")
    with pytest.raises(KeyError, match="no configuration"):
        cells.load_config_file(BENCH, "not-a-config")


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cell_names
    for x in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] and "\t" not in x["why"]
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_cells_chips_and_run_seconds():
    cellsn = BENCH["workloads"]
    assert all(w["chips"] in (1, 4) for w in cellsn)
    assert sum(w["chips"] == 4 for w in cellsn) <= max(1, len(cellsn) // 4)
    assert len({(w["config"], w["traffic"]) for w in cellsn}) == len(cellsn)
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells has to fit 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
    assert not any(w.startswith("/") or ".." in w for w in BENCH["command"])


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_header(name):
    cfg = cells.load_config_file(BENCH, name)
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) and len(entry["reduced"]) <= 16
    assert cfg["assumed"] and cfg["deployment"] and cfg["published"]
    # published widths are kept: the file's own ``widths`` map, else the default
    # one, every key of which the model block then has (``load_config_file``
    # refuses a fault too; said here so that a failure names it)
    assert cells.header_faults(cfg, entry["reduced"]) == []


@pytest.mark.parametrize("kind", sorted(cells.CONTRACT))
@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_modules_keep_the_contract(name, kind):
    cfg = cells.load_config_file(BENCH, name)
    module = cells.load_modules(cfg)[kind]
    for function in cells.CONTRACT[kind]:
        assert callable(getattr(module, function)), (module.__name__, function)
    if kind not in (cfg.get("modules") or {}):
        assert module.__name__ == cells.DEFAULT_MODULES[kind]
    if kind == "reference":
        # it imports nothing of the program
        assert "neuronx_distributed_training_tpu" not in open(module.__file__).read()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_operations_count_what_the_cell_needs(name):
    c = cells.load_cell(name)
    need = c.operations.train_flops_per_token(c.model, c.traffic["seq_length"])
    assert need["total"] > 0
    calls = c.operations.kernel_calls(c.model, c.traffic, 1)
    assert calls and all(
        k["flops"] > 0 and k["bytes"] > 0 and k["calls"] >= 1 for k in calls.values())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_with_its_metrics(name):
    c = cells.load_cell(name)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "tokens_per_s_per_chip"}
    assert c.per_layer and all(m["reader"] in stems("readers") for m in c.per_layer)
    t = c.traffic
    assert t["global_batch_size"] % t["micro_batches"] == 0
    assert {"seq_length", "micro_batch_size", "tokens", "check_steps",
            "warmup_steps", "trace_steps", "why"} <= set(t)


HARNESS = [HERE / "run.py", *sorted((HERE / "harness").glob("*.py")),
           *sorted((HERE / "readers").glob("*.py"))]


def test_harness_names_no_model_and_no_cell():
    # harness/cell.py holds the loaders of references/, operations/ and limits/
    words = re.compile(r"mistral|mixtral|llama|pretrain-", re.I)
    for path in HARNESS:
        for n, line in enumerate(path.read_text().splitlines(), 1):
            assert not words.search(line), f"{path.name}:{n}: {line.strip()}"


def test_harness_reaches_a_configuration_through_the_cell_alone():
    """No per-configuration function is called through a module imported by
    name (no reference module is imported at all: where the name stands, it is
    an argument); the one default lives in the loader.  ``flops.peaks_for`` and
    ``flops.roofline_seconds`` are the chip's and stay a plain import."""
    by_name = re.compile(
        r"\bflops\.(train_flops_per_token|flash_call|kernel_calls|model_dims)\b")
    imports = re.compile(r"^\s*(from benchmark import .*\breference\b|"
                         r"import benchmark\.reference|from benchmark\.reference)")
    for path in HARNESS + sorted((HERE / "tools").glob("*.py")):
        for n, line in enumerate(path.read_text().splitlines(), 1):
            assert not by_name.search(line), f"{path.name}:{n}: {line.strip()}"
            assert not imports.search(line), f"{path.name}:{n}: {line.strip()}"
    defaults = [p.name for p in HARNESS
                if any(d in p.read_text() for d in cells.DEFAULT_MODULES.values())]
    assert defaults == ["cell.py"]


def test_limits_come_from_one_place(tmp_path):
    from benchmark.harness import check as checks

    (tmp_path / "benchmark" / "limits").mkdir(parents=True)
    table = tmp_path / "benchmark" / "limits.json"
    table.write_text(json.dumps({"in-table": {"loss_gap": 1.0}}))
    (tmp_path / "benchmark" / "limits" / "own-file.json").write_text(
        json.dumps({"loss_gap": 2.0}))
    assert checks.limits_for("in-table", tmp_path) == {"loss_gap": 1.0}
    assert checks.limits_for("own-file", tmp_path) == {"loss_gap": 2.0}
    with pytest.raises(KeyError, match="has limits for 'absent'"):
        checks.limits_for("absent", tmp_path)
    (tmp_path / "benchmark" / "limits" / "in-table.json").write_text("{}")
    with pytest.raises(ValueError, match="in benchmark/limits.json and in"):
        checks.limits_for("in-table", tmp_path)
    # every configuration has limits in exactly one of the two places, and
    # the table's own names are held to the table
    with open(HERE / "limits.json") as f:
        table = json.load(f)
    assert {c: checks.limits_for(c) for c in table} == table
    assert set(table) | stems("limits") == set(CONFIG_FILES)
    assert not set(table) & stems("limits")
