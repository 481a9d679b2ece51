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
    return {p.stem for p in (HERE / sub).iterdir() if p.is_file()}


def test_every_data_file_is_referenced():
    assert {(ROOT / c["file"]).stem for c in BENCH["configs"]} == stems("configs")
    assert {w["traffic"] for w in BENCH["workloads"]} == stems("traffic")
    assert {m["name"] for m in BENCH["per_layer"]} == stems("layer_metrics")
    assert {w["config"] for w in BENCH["workloads"]} == {c["name"] for c in BENCH["configs"]}
    readers = {cells.load_layer_metric(m["name"])["reader"] for m in BENCH["per_layer"]}
    assert readers == stems("readers") - {"__init__"}


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
    assert cfg["assumed"] and cfg["deployment"]
    width = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head_|top_k|per_tok")
    assert not any(width.search(k) for k in entry["reduced"])
    # published widths are kept
    m, pub = cfg["trainer_config"]["model"], cfg["published"]
    for k in ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size", "rope_theta"):
        assert m[k] == pub[k], k
    if "moe" in m:
        assert m["moe"]["num_experts"] == pub["num_local_experts"]
        assert m["moe"]["top_k"] == pub["num_experts_per_tok"]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_with_its_metrics(name):
    c = cells.load_cell(name)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "tokens_per_s_per_chip"}
    assert c.per_layer and all(m["reader"] in stems("readers") for m in c.per_layer)
    t = c.traffic
    assert t["global_batch_size"] % t["micro_batches"] == 0
    assert {"seq_length", "micro_batch_size", "tokens", "check_steps",
            "warmup_steps", "trace_steps", "why"} <= set(t)


def test_harness_names_no_model_and_no_cell():
    words = re.compile(r"mistral|mixtral|llama|pretrain-", re.I)
    for path in [HERE / "run.py", *sorted((HERE / "harness").glob("*.py")),
                 *sorted((HERE / "readers").glob("*.py"))]:
        for n, line in enumerate(path.read_text().splitlines(), 1):
            assert not words.search(line), f"{path.name}:{n}: {line.strip()}"
