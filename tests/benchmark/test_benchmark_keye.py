"""The ``keye-vl-2.0-30b-a3b`` configuration's own files, beyond what the
tests parametrised over every configuration hold (header, modules, fp8
control): the top level is the catalog's config but for what ``reduced``
lists, ``sa_config`` whole and held width for width; the cut is the one the
header states; its operations count REQUIRED work part by part (selected
pairs alone for the main attention, every causal pair for the index scores);
the cell rehearses on the CPU with the selection running; and dense attention
in the sparse one's place and an omitted ``L_I`` are each caught by the
limits."""

import copy
import dataclasses
import json
import time

import pytest
from benchmark_toy import toy

from benchmark.harness import cell as cells
from benchmark.harness import check as checks
from benchmark.harness import drive

CELL = "keye-vl2-30b-pretrain-8k-ep8"
BENCH = cells.load_benchmark()
NEW_METRICS = ["dsa_indexer_ms_per_step", "dsa_select_ms_per_step",
               "dsa_indexer_loss_ms_per_step", "dsa_select_roofline_pct", "dsa_kept_pairs_share"]
JOINED = ["attention_ms_per_step", "moe_ms_per_step", "moe_router_ms_per_step",
          "moe_load_max_share_p95", "ce_head_ms_per_step", "optimizer_ms_per_step",
          "forward_ms_per_step", "backward_ms_per_step", "unscoped_device_pct",
          "flash_ms_per_step", "flash_roofline_pct"]
#: the toy that selects: 16 of up to 64 keys in chunks of 16 queries
SA = dict(topk=16, indexer_num_heads=2, indexer_head_dim=8, indexer_num_kv_heads=1,
          q_chunk_size=16, kv_chunk_size=16)
TOY_FILE = json.loads((cells.ROOT / "tests/benchmark/toy_limits_keye.json").read_text())


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def selecting_toy():
    """The cell at toy widths with ``sa_config`` cut so that it selects, under
    a name of its own (the run directory is the cell's, and
    test_benchmark_rehearsal.py rehearses the cell in another process)."""
    base = toy(cells.load_cell(CELL), seq=64)
    cfg = copy.deepcopy(base.config)
    cfg["trainer_config"]["model"]["sa_config"] = SA
    return dataclasses.replace(base, config=cfg, name=f"{CELL}-own")


def test_the_top_level_is_the_source_but_for_what_reduced_lists(cell):
    cfg = cell.config
    catalog = {k: v for k, v in cfg["published"].items() if "." not in k}
    assert all(k in cfg for k in catalog)
    cut = {k for k in catalog if cfg[k] != catalog[k]}
    assert cut == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["num_experts_held"] == [0, 16] and "num_experts_held" in cfg["reduced"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size",
                                   "global_batch_size", "max_steps"}
    assert catalog["num_hidden_layers"] == 48 and catalog["vocab_size"] == 151936
    assert catalog["sa_config"] == cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    # the header check reads ``published`` flat: the nested numbers once more
    for key, value in catalog["sa_config"].items():
        assert cfg["published"][f"sa_config.{key}"] == value
    assert sorted(cfg["assumed"])[:8] == [
        "a_chunk_sizes", "b_indexer", "c_objective", "d_head_norms", "e_router",
        "f_text_only", "g_intermediate_size", "h_init_and_optimizer"]


def test_the_model_block_is_six_layers_at_every_width(cell):
    model, source, widths = cell.model, cell.config["published"], cell.config["widths"]
    assert model["num_hidden_layers"] == cell.config["num_hidden_layers"] == 6 >= 4
    assert model["num_experts"] == source["num_experts"] == 128        # the router's width
    assert model["num_experts_held"] == [0, 16]                        # at least 8
    assert model["vocab_size"] * 8 == source["vocab_size"] and model["vocab_size"] % 128
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "num_experts", "rope_theta", "rms_norm_eps",
                "max_position_embeddings"):
        assert model[key] == source[widths[key]], key
    for key in ("topk", "indexer_num_heads", "indexer_head_dim", "indexer_num_kv_heads",
                "q_chunk_size", "kv_chunk_size"):
        assert widths[f"sa_config.{key}"] == f"sa_config.{key}"
        assert model["sa_config"][key] == source[f"sa_config.{key}"], key
    assert widths["num_experts_held"] == "num_experts"
    assert cells.header_faults(cell.config, list(cell.config["reduced"])) == []
    for key in ("norm_topk_prob", "rope_scaling", "tie_word_embeddings", "mlp_only_layers",
                "decoder_sparse_step", "use_sliding_window", "hidden_act"):
        assert model[key] == source[key], key
    assert "8 chips" in cell.config["deployment"]
    t = cell.traffic
    assert cell.traffic["overrides"] == {"model.optim.lr": 1.875e-05}
    assert (t["seq_length"], t["micro_batch_size"], t["global_batch_size"],
            t["micro_batches"]) == (8192, 2, 2, 1)


def test_the_cell_reports_the_rate_and_not_the_step_tail(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(JOINED) <= names
    assert "mlp_ms_per_step" not in names           # no layer opens ``mlp``
    for m in BENCH["per_layer"]:
        # membership only: a later cell joins these lists without an edit here
        if m["name"] in NEW_METRICS:
            assert CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip"
        if m["name"] in JOINED:
            assert CELL in m["workloads"]
    for name, reader, args in (
            ("dsa_indexer_ms_per_step", "inner_scope", {"component": "indexer"}),
            ("dsa_select_ms_per_step", "inner_scope", {"component": "select"}),
            ("dsa_indexer_loss_ms_per_step", "inner_scope", {"component": "indexer_loss"}),
            ("dsa_select_roofline_pct", "scope_roofline",
             {"component": "select", "calls": "select_calls"}),
            ("dsa_kept_pairs_share", "metrics_jsonl",
             {"key": "dsa/kept_pairs_share", "stat": "mean"})):
        spec = cells.load_layer_metric(name)
        assert (spec["reader"], spec["args"]) == (reader, args)
    from neuronx_distributed_training_tpu.telemetry import spans
    assert {"indexer", "select", "indexer_loss"} <= set(spans.FAMILY_SCOPES["attention"])


def test_the_operations_count_required_work_part_by_part(cell):
    ops, model, seq = cell.operations, cell.model, cell.traffic["seq_length"]
    need = ops.train_flops_per_token(model, seq)
    parts = ("qkv_and_o", "selected_scores", "indexer_projections", "index_scores", "router",
             "held_experts", "head")
    assert need["total"] == pytest.approx(sum(need[p] for p in parts), rel=1e-12)
    h, H, G, d = 2048, 32, 4, 128
    kept = (2048 * 2049 / 2 + (seq - 2048) * 2048) / seq
    assert need["kept_pairs_share"] == pytest.approx(kept / ((seq + 1) / 2), rel=1e-12)
    assert round(need["kept_pairs_share"], 4) == 0.4375
    assert need["qkv_and_o"] == 6 * 6 * (h * (H + 2 * G) * d + H * d * h)
    assert need["selected_scores"] == pytest.approx(6 * 3 * 2 * H * 2 * d * kept, rel=1e-12)
    # a detached input: forward and the weights' gradient, none to the input
    assert need["indexer_projections"] == 6 * 4 * h * (16 * 64 + 64 + 16)
    assert need["index_scores"] == pytest.approx(6 * 3 * 2 * 16 * 64 * (seq + 1) / 2, rel=1e-12)
    assert need["held_slots_per_token"] == 8 * 16 / 128
    assert need["held_experts"] == 6 * 6 * 1.0 * 3 * h * 768
    assert need["router"] == 6 * 6 * h * 128 and need["head"] == 6 * h * 18992
    assert 1.8e9 < need["total"] < 1.85e9
    # a masked-dense kernel is not credited with the pairs it throws away
    dense = {**model, "sa_config": {**model["sa_config"], "topk": seq}}
    assert ops.train_flops_per_token(dense, seq)["selected_scores"] == pytest.approx(
        need["selected_scores"] / need["kept_pairs_share"], rel=1e-12)
    calls = ops.kernel_calls(model, cell.traffic, 1)
    pairs = 2 * H * seq * kept
    assert calls["fwd"]["flops"] == pytest.approx(2 * pairs * 2 * d, rel=1e-12)
    assert {k: v["calls"] for k, v in calls.items()} == {"fwd": 6, "dq": 6, "dkv": 6}
    select = ops.select_calls(model, cell.traffic, 1)["select"]
    assert select == {"flops": 0.0, "bytes": 2 * seq * seq / 2 * 4, "calls": 12}


def test_the_limits_name_the_indexers_leaves(cell):
    import re

    import jax

    limits = checks.limits_for(cell.config_name)
    # the leaves whose first gradient hangs on a discrete choice (the router's)
    # or comes from the indexer's loss alone; each limit's reason is in the file
    assert limits["routed_leaves"] == "attn/indexer/|mlp/router/w" and limits.pop("why")
    assert set(limits) == {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf", "routed_leaves",
                           "grad1_routed_worst_leaf", "dparam_routed_worst_leaf"}
    # under a state left unchanged and an omitted L_I (both read 1)
    assert limits["dparam_worst_leaf"] < 1.0 and limits["dparam_routed_worst_leaf"] < 1.0
    assert limits["grad1_routed_worst_leaf"] < 1.0
    for group in (TOY_FILE, TOY_FILE["selecting"]):
        assert set(group["limits"]) == set(limits) and group["why"]
    names = cell.reference.leaf_names(jax.eval_shape(
        lambda: cell.reference.init_params(toy(cell).model, jax.random.PRNGKey(0))))
    routed = [n for n in names if re.search(limits["routed_leaves"], n)]
    assert routed == [f"layers/attn/indexer/{leaf}" for leaf in (
        "k_norm/bias", "k_norm/scale", "weights/w", "wk/w", "wq/w")] + ["layers/mlp/router/w"]
    assert TOY_FILE["limits"]["routed_leaves"] == "attn/indexer/"      # the toys' own grouping


# -- the cell end to end on the CPU, the selection running ---------------------------


def test_the_cell_rehearses_with_the_selection_running(capsys):
    """The untraced line on the CPU at toy widths: ``correct``, the rate and
    ``setup_s`` and no ``step_ms_p95``; the indexer's leaves compared in a
    group of their own.  (A window of 5 s, as the other held cells' own files:
    beside five other workers a toy step can outlast 1 s, and a window that
    closes before a step completed in it is an error.)"""
    result = drive.run_cell(selecting_toy(), seed=2**31 + 17, seconds=5.0, trace=False,
                            t_process=time.perf_counter(), require_tpu=False,
                            limits=TOY_FILE["selecting"]["limits"])
    out = capsys.readouterr().out
    assert result["correct"] is True, "\n".join(
        l for l in out.splitlines() if l.startswith("check"))
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert "cut: num_experts_held" in out and "assumed: b_indexer" in out
    assert len(result["compared"]["limits"]) == 7
    assert "layers/attn/indexer/wq/w" in result["compared"]["leaves"]["grad1"]


@pytest.fixture(scope="module")
def three_steps():
    """``reference.run`` over the selecting toy's three checked steps, with
    ``left_out``; the whole one kept for the module."""
    cell = selecting_toy()
    as_run = drive.merged_config(
        cell, drive.overrides_for(cell, 3, False, drive.WORK / "unused"))
    model, kept = as_run["model"], {}

    def run(left_out=()):
        if left_out not in kept:
            kept[left_out] = cell.reference.run(
                model, model["optim"], as_run["trainer"]["gradient_clip_val"],
                drive.check_tokens(cell, model, 3), 3, left_out=left_out)
        return kept[left_out]

    return run


@pytest.mark.parametrize("left_out, fails", [
    ("selection", {"loss_gap_step1", "grad1_worst_leaf"}),
    ("indexer_loss", {"loss_gap_step1", "grad1_routed_worst_leaf",
                      "dparam_routed_worst_leaf"})])
def test_an_omission_in_the_programs_place_is_not_correct(left_out, fails, three_steps, capsys):
    """The reference with dense attention in the sparse one's place, or with no
    ``L_I``, held against the reference as it is: each fails the limits the
    sound program passes."""
    ok, compared = checks.compare(three_steps((left_out,)), three_steps(),
                                  TOY_FILE["selecting"]["limits"])
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert not ok and fails <= failed, (failed, compared)
