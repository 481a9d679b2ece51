"""The ``kanana-2-30b-a3b`` configuration's own files, beyond what the tests
parametrised over every configuration hold (header, modules, fp8 control):
the top level is the catalog's config but for what ``reduced`` lists, the cut
is the one the header states, its operations count adds up part by part and
counts the forward kernel's calls as a traced step runs them, the cell
rehearses on the CPU as it is, and an omitted bias update and a state left
unchanged are caught."""

import dataclasses
import json
import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark import flops
from benchmark.harness import cell as cells
from benchmark.harness import drive

CELL = "kanana2-30b-pretrain-8k-ep8"
BENCH = cells.load_benchmark()
NEW_METRICS = ["mla_latent_ms_per_step", "moe_router_ms_per_step", "moe_load_max_share_p95"]
JOINED = ["attention_ms_per_step", "mlp_ms_per_step", "moe_ms_per_step", "ce_head_ms_per_step",
          "optimizer_ms_per_step", "forward_ms_per_step", "backward_ms_per_step",
          "unscoped_device_pct", "flash_ms_per_step", "flash_roofline_pct"]


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def rehearse(**kw):
    """``test_benchmark_rehearsal.rehearse`` for this cell with a window of
    5 s: at toy widths it keeps the published latent dims and counts (a router
    128 wide, experts of 768), and a step of it can outlast 1 s on a loaded
    machine."""
    # a name of its own: the run directory is the cell's, and
    # test_benchmark_rehearsal.py rehearses the cell in another process
    toy_cell = dataclasses.replace(toy(cells.load_cell(CELL), seq=64), name=f"{CELL}-own")
    return drive.run_cell(toy_cell, seed=2**31 + 17, seconds=5.0,
                          t_process=time.perf_counter(), require_tpu=False,
                          limits=toy_limits(toy_cell), **{"trace": False, **kw})


def test_the_top_level_is_the_source_but_for_what_reduced_lists(cell):
    cfg = cell.config
    source = cfg["published"]
    assert all(k in cfg for k in source)
    cut = {k for k in source if cfg[k] != source[k]}
    assert cut == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["num_experts_held"] == [0, 16] and "num_experts_held" in cfg["reduced"]
    assert source["qk_head_dim"] == source["qk_nope_head_dim"] + source["qk_rope_head_dim"] == 192
    assert source["q_lora_rank"] is None and source["rope_scaling"] is None
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size",
                                   "global_batch_size", "max_steps"}


def test_the_model_block_is_layer_0_and_five_sparse_layers_at_every_width(cell):
    model, source = cell.model, cell.config["published"]
    assert model["num_hidden_layers"] == cell.config["num_hidden_layers"] == 6
    assert model["first_k_dense_replace"] == source["first_k_dense_replace"] == 1
    assert model["n_routed_experts"] == source["n_routed_experts"] == 128   # the router's width
    assert model["num_experts_held"] == [0, 16]
    assert model["vocab_size"] * 8 == source["vocab_size"] and model["vocab_size"] % 128
    widths = cell.config["widths"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim", "qk_head_dim",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "num_experts_per_tok", "n_routed_experts", "n_shared_experts",
                "routed_scaling_factor", "rope_theta", "rms_norm_eps",
                "max_position_embeddings"):
        assert model[key] == source[widths[key]], key
    assert widths["num_experts_held"] == "n_routed_experts"
    for key in ("scoring_func", "topk_method", "rope_interleave", "norm_topk_prob",
                "q_lora_rank", "rope_scaling", "n_group", "topk_group"):
        assert model[key] == source[key], key
    assert "8 chips" in cell.config["deployment"]


def test_the_traffic_overrides_what_assumed_names(cell):
    assert cell.traffic["overrides"] == {"model.optim.lr": 1.875e-05}
    assert "lr" in cell.config["assumed"] and "warmup_steps" in cell.config["assumed"]
    assert "router_bias_update_rate" in cell.config["assumed"]
    assert cell.model["router_bias_update_rate"] == 0.001
    assert cell.model["optim"]["sched"]["warmup_steps"] == 100
    t = cell.traffic
    assert (t["seq_length"], t["micro_batch_size"], t["global_batch_size"],
            t["micro_batches"]) == (8192, 2, 2, 1)


def test_the_cell_reports_the_rate_and_not_the_step_tail(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # membership only: a later cell joins these lists without an edit here
    assert set(NEW_METRICS) | set(JOINED) <= names
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED:
            assert CELL in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "tokens_per_s_per_chip"
    for name, reader, args in (
            ("mla_latent_ms_per_step", "inner_scope", {"component": "mla_latent"}),
            ("moe_router_ms_per_step", "inner_scope", {"component": "router"}),
            ("moe_load_max_share_p95", "metrics_jsonl",
             {"key": "moe/load_max_share", "stat": "p95"})):
        spec = cells.load_layer_metric(name)
        assert (spec["reader"], spec["args"]) == (reader, args)


def test_the_operations_add_up_part_by_part(cell):
    ops, model, seq = cell.operations, cell.model, cell.traffic["seq_length"]
    need = ops.train_flops_per_token(model, seq)
    parts = ("q_and_o", "latent", "scores", "dense_mlp", "router", "shared_experts",
             "held_experts", "head")
    assert need["total"] == pytest.approx(sum(need[p] for p in parts), rel=1e-12)
    h, H = 2048, 32
    assert need["q_and_o"] == 6 * 6 * (h * H * 192 + H * 128 * h)
    assert need["latent"] == 6 * 6 * (h * (512 + 64) + 512 * H * (128 + 128))
    assert need["scores"] == pytest.approx(6 * 3 * 2 * H * (192 + 128) * (seq + 1) / 2, rel=1e-12)
    assert need["held_slots_per_token"] == 6 * 16 / 128
    assert need["held_experts"] == 5 * 6 * 0.75 * 3 * h * 768
    assert need["shared_experts"] == 5 * 6 * 3 * h * 2 * 768
    assert need["router"] == 5 * 6 * h * 128
    assert need["dense_mlp"] == 6 * 3 * h * 6144 and need["head"] == 6 * h * 16032
    assert 3.2e9 < need["total"] < 3.4e9
    assert 0.44 < need["scores"] / need["total"] < 0.48          # the kernels do most
    # the kernels: per visible pair and head 2 (d_qk + d_v), 2 (2 d_qk + d_v), 2 (2 d_qk + 2 d_v)
    calls = ops.kernel_calls(model, cell.traffic, 1)
    pairs = 2 * H * seq * (seq + 1) / 2
    assert calls["fwd"]["flops"] == pytest.approx(2 * pairs * 320, rel=1e-12)
    assert calls["dq"]["flops"] == pytest.approx(2 * pairs * 512, rel=1e-12)
    assert calls["dkv"]["flops"] == pytest.approx(2 * pairs * 640, rel=1e-12)
    # a traced step runs the dense layer's forward kernel once (a scan of one
    # layer: the rerun is merged), the five sparse layers' twice
    assert {k: v["calls"] for k, v in calls.items()} == {"fwd": 11, "dq": 6, "dkv": 6}
    assert ops.kernel_calls({**model, "activations_checkpoint_granularity": None},
                            cell.traffic, 1)["fwd"]["calls"] == 6
    qk, vo, row = 2 * H * seq * 192 * 2, 2 * H * seq * 128 * 2, 2 * H * seq * 4
    assert calls["fwd"]["bytes"] == 2 * qk + 2 * vo + row
    assert calls["dkv"]["bytes"] == 3 * qk + 3 * vo + 2 * row
    peaks = flops.peaks_for("TPU v5 lite")
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "compute"
               for k in calls.values())


def test_the_limits_name_the_routed_leaves_of_this_tree(cell):
    from benchmark.harness import check as checks

    limits = checks.limits_for(cell.config_name)
    # the group of its own is the selection bias's: it moves in whole steps of
    # 0.001 and takes no gradient; router and experts are held with every
    # other weight (PERF.md section 6)
    assert limits["routed_leaves"] == "mlp/router/bias"
    assert limits["grad1_routed_worst_leaf"] <= 1e-6
    assert limits["dparam_worst_leaf"] * 10 < limits["dparam_routed_worst_leaf"]
    assert set(limits) == {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf", "routed_leaves",
                           "grad1_routed_worst_leaf", "dparam_routed_worst_leaf"}
    # under a state left unchanged (1.0), with the more room above the readings
    assert limits["dparam_worst_leaf"] < 1.0 and limits["dparam_routed_worst_leaf"] < 1.0
    toy_file = json.loads((cells.ROOT / "tests/benchmark/toy_limits_kanana.json").read_text())
    assert set(toy_file["limits"]) == set(limits) and toy_file["why"]
    import re
    names = cell.reference.leaf_names(cell.reference.init_params(
        toy(cell).model, __import__("jax").random.PRNGKey(0)))
    routed = [n for n in names if re.search(limits["routed_leaves"], n)]
    assert routed == ["layers/sparse/mlp/router/bias"]


# -- the cell end to end on the CPU, traced and with the timed path broken -------


def test_the_cell_rehearses_with_the_rate_and_no_step_tail(capsys):
    """The untraced line on the CPU at toy widths: ``correct``, the rate and
    ``setup_s``; no ``step_ms_p95`` (a step's time follows the rows two
    sequences send to the held experts), whose own sample is still printed."""
    result = rehearse()
    out = capsys.readouterr().out
    assert result["correct"] is True, "\n".join(
        l for l in out.splitlines() if l.startswith("check"))
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert "step time:" in out and "cut: num_experts_held" in out
    assert len(result["compared"]["limits"]) == 7
    assert "layers/sparse/mlp/router/bias" in result["compared"]["leaves"]["dparam"]


def test_traced_rehearsal_reads_the_counters_and_no_device_number():
    """``--trace 1`` off a TPU: the held rows' share and the largest load are
    counters of the program, read from the window's rows; the scopes' times
    and the roofline share are device numbers and are left out."""
    from test_benchmark_rehearsal import DEVICE_METRICS

    result = rehearse(trace=True)
    assert result["correct"] is True
    counters = {"moe_load_max_share_p95"}
    assert {"compile_s", "compiles_in_window"} | counters <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["moe_load_max_share_p95"]["value"] < 128 / 6
    assert not ((DEVICE_METRICS | set(NEW_METRICS) | set(JOINED)) - counters) & set(
        result["metrics"])
    assert "busy_s" not in result["device"] and list(result)[-1] == "compared"


def test_a_state_left_unchanged_is_not_correct(capsys):
    """Under the warm-up the weights' change is small and the bias's is three
    steps of 0.001: a step that returns its state unchanged reads 1 against
    both."""
    from test_benchmark_rehearsal import stuck

    result = rehearse(tamper=stuck)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert {"dparam_worst_leaf", "dparam_routed_worst_leaf"} <= failed, failed
    assert result["compared"]["dparam_routed_worst_leaf"] == pytest.approx(1.0, abs=1e-2)


def no_bias_update(trainer):
    """A step whose selection bias never moves: the rule left out."""
    real = trainer.train_step

    def step(params, opt_state, batch, key):
        bias = params["layers"]["sparse"]["mlp"]["router"]["bias"].copy()
        params, opt_state, metrics = real(params, opt_state, batch, key)
        params["layers"]["sparse"]["mlp"]["router"]["bias"] = bias
        return params, opt_state, metrics

    trainer.train_step = step


def test_an_omitted_bias_update_is_not_correct(capsys):
    result = rehearse(tamper=no_bias_update)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert failed == {"dparam_routed_worst_leaf"}, failed
    leaves = result["compared"]["leaves"]["dparam"]
    assert max(leaves, key=leaves.get) == "layers/sparse/mlp/router/bias"
    assert leaves["layers/sparse/mlp/router/bias"] == pytest.approx(1.0, abs=1e-6)
