"""What a cell's data files owe each other, and the record of the regime a
cell runs in: every override a traffic file makes is a departure that its
configuration's header names (PR 33: the override that collapsed the Mixtral
router was a cut written in one file and paid for in another); a key that
counts layers is no width; the two flash metrics list the cells that run the
kernels; the two metrics of the expert exchange's regime read the window's
rows of ``metrics.jsonl``; the metrics of the host's stalls are read in every
cell."""

import pytest

from benchmark.harness import cell as cells
from benchmark.readers import metrics_jsonl

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
EP4 = "mixtral8x7b-pretrain-4k-ep4"


def unnamed_overrides(traffic: dict, config: dict) -> list:
    """Keys of the traffic's ``overrides`` that neither ``reduced`` nor
    ``assumed`` of the configuration names, by the dotted key or its last
    part."""
    named = set(config["reduced"]) | set(config["assumed"])
    return [k for k in traffic.get("overrides") or {}
            if k not in named and k.rsplit(".", 1)[-1] not in named]


@pytest.mark.parametrize("name", CELLS)
def test_every_override_is_named_by_the_configuration(name):
    cell = cells.load_cell(name)
    assert unnamed_overrides(cell.traffic, cell.config) == []
    # and the batch the traffic sets is a cut the header owns up to
    assert "global_batch_size" in cell.config["reduced"]


def test_an_override_nobody_names_is_found():
    cell = cells.load_cell(EP4)
    traffic = {**cell.traffic, "overrides": {"model.moe.router_aux_loss_coef": 0.0,
                                             "model.optim.sched.max_steps": 7}}
    assert unnamed_overrides(traffic, cell.config) == ["model.moe.router_aux_loss_coef"]


@pytest.mark.parametrize("key, width", [
    ("hidden_size", True), ("num_hidden_layers", False), ("num_layers", False),
    ("mtp.num_hidden_layers", False), ("num_experts_per_tok", True),
    ("moe.top_k", True), ("kv_lora_rank", True), ("head_dim", True),
    ("hidden_layers_dim", True), ("layers_hidden_size", True),
    ("global_batch_size", False), ("lr", False)])
def test_a_key_that_counts_layers_is_no_width(key, width):
    assert cells.names_a_width(key) is width
    cfg = cells.load_config_file(BENCH, "mistral-7b")
    faults = cells.header_faults(cfg, [*cfg["reduced"], key])
    assert (f"reduced names a width: {key}" in faults) is width


@pytest.mark.parametrize("metric", ["flash_ms_per_step", "flash_roofline_pct"])
def test_the_flash_metrics_list_the_cells_that_run_the_kernels(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    runs_flash = [w["name"] for w in BENCH["workloads"]
                  if cells.load_cell(w["name"]).model["fusions"]["flash_attention"]]
    assert entry["workloads"] == runs_flash == CELLS


ROWS = [{"step": s, "moe/row_bound": b, "moe/recv_rows_share_max": share}
        for s, (b, share) in enumerate(
            [(0, 1.05), (0, 1.25), (0, 1.10), (1, 2.50)] + [(0, 1.0)] * 16, start=6)]


@pytest.mark.parametrize("metric, value", [
    ("moe_weights_way_step_pct", 5.0), ("moe_recv_share_max_p95", 1.25)])
def test_the_regime_metrics_read_the_window_rows(metric, value):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [EP4] and entry["moves"] == "tokens_per_s_per_chip"
    spec = cells.load_layer_metric(metric)
    assert spec["reader"] == "metrics_jsonl"
    assert metrics_jsonl.read({"rows": ROWS}, **spec["args"]) == pytest.approx(value)
    # a dense cell's rows have no such column: nothing to read, not 0
    assert metrics_jsonl.read({"rows": [{"step": 6, "loss": 1.0}]}, **spec["args"]) is None
    assert metric in {m["name"] for m in cells.load_cell(EP4).per_layer}
    assert metric not in {m["name"] for m in cells.load_cell("mistral7b-pretrain-4k").per_layer}


@pytest.mark.parametrize("metric", [
    "compiles_in_window", "data_wait_ms_p95", "log_metrics_ms_p95"])
def test_a_stall_on_the_host_is_read_in_every_cell(metric):
    """They move the rate, which every cell reports (a stall of one step in a
    hundred never reaches the 95th percentile), and carry no list: a cell a
    later PR adds reports them too."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "tokens_per_s_per_chip" and "workloads" not in entry
    assert all(metric in {m["name"] for m in cells.load_cell(c).per_layer}
               for c in CELLS)


def test_a_per_layer_metric_is_read_where_its_entry_says():
    """One rule: the entry's list, or every cell.  Each listed cell reports
    the end-to-end metric the entry moves."""
    for cell_name in CELLS:
        cell = cells.load_cell(cell_name)
        reports = {m["name"] for m in cell.end_to_end}
        assert {m["moves"] for m in cell.per_layer} <= reports, cell_name
        assert [m["name"] for m in cell.per_layer] == [
            m["name"] for m in BENCH["per_layer"]
            if cell_name in m.get("workloads", CELLS)]
