"""The ``lfm2-24b-a2b`` configuration's own files, beyond what the tests
parametrised over every configuration hold (header, modules, fp8 control):
the top level is the catalog's config but for what ``reduced`` lists, the cut
is the one the header states, its operations count adds up part by part and
counts the forward kernel's calls as a traced step runs them (once a layer
application), the cell rehearses on the CPU as it is, and an omitted bias
update and a state left unchanged are caught."""

import dataclasses
import json
import re
import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark import flops
from benchmark.harness import cell as cells
from benchmark.harness import drive

CELL = "lfm2-24b-pretrain-8k-ep8"
BENCH = cells.load_benchmark()
NEW_METRICS = ["short_conv_ms_per_step", "conv_gate_ms_per_step", "qk_norm_ms_per_step",
               "conv_gate_roofline_pct"]
JOINED = ["attention_ms_per_step", "mlp_ms_per_step", "moe_ms_per_step", "ce_head_ms_per_step",
          "optimizer_ms_per_step", "forward_ms_per_step", "backward_ms_per_step",
          "unscoped_device_pct", "flash_ms_per_step", "flash_roofline_pct",
          "moe_router_ms_per_step", "moe_load_max_share_p95"]
SPARSE = ("full_sparse", "conv_sparse")
DEPTH = 8


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def rehearse(**kw):
    """``test_benchmark_rehearsal.rehearse`` for this cell with a window of
    5 s: at toy widths it keeps the published counts (a router 64 wide,
    experts of 1536, 3 taps), and a step of it can outlast 1 s on a loaded
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
    catalog = {k: v for k, v in source.items() if "." not in k}     # the dotted key repeats
    assert all(k in cfg for k in catalog)
    cut = {k for k in catalog if cfg[k] != catalog[k]}
    assert cut == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["num_experts_held"] == [0, 8] and "num_experts_held" in cfg["reduced"]
    assert cfg["layer_types"] == source["layer_types"] and len(source["layer_types"]) == 40
    assert source["layer_types"].count("full_attention") == 10
    assert [i for i, t in enumerate(source["layer_types"]) if t == "full_attention"] == list(
        range(2, 40, 4))
    assert cfg["rope_parameters"] == source["rope_parameters"]
    assert source["rope_parameters.rope_theta"] == source["rope_parameters"]["rope_theta"] == 1000000
    assert "head_dim" not in source and "head_dim" not in cell.model     # derived: 2048 / 32
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size",
                                   "global_batch_size", "max_steps"}


def test_the_model_block_is_both_dense_layers_and_two_periods_at_every_width(cell):
    model, source = cell.model, cell.config["published"]
    assert model["num_hidden_layers"] == cell.config["num_hidden_layers"] == DEPTH
    assert model["layer_types"] == source["layer_types"][:DEPTH] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
    assert model["num_dense_layers"] == source["num_dense_layers"] == 2
    assert model["num_experts"] == source["num_experts"] == 64         # the router's width
    assert model["num_experts_held"] == [0, 8]
    assert model["vocab_size"] * 8 == source["vocab_size"]
    assert model["hidden_size"] // model["num_attention_heads"] == 64  # half a lane width
    widths = cell.config["widths"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "num_experts_per_tok", "num_experts", "num_dense_layers",
                "routed_scaling_factor", "norm_eps", "max_position_embeddings"):
        assert model[key] == source[widths[key]], key
    assert model["rope_parameters"]["rope_theta"] == source[widths["rope_parameters.rope_theta"]]
    assert widths["num_experts_held"] == "num_experts"
    for key in ("conv_bias", "norm_topk_prob", "use_expert_bias"):
        assert model[key] == source[key], key
    assert model["tie_word_embeddings"] is True and "tie_word_embeddings" in cell.config["assumed"]
    deployment = cell.config["deployment"]
    assert "8 chips" in deployment and "42 %" in deployment and "1/8 of the rows" in deployment
    assert "42%" in cell.why and "6% published" in cell.why


def test_the_traffic_is_kananas_file_and_overrides_what_assumed_names(cell):
    assert cell.traffic_name == "pretrain-8k-mb2"
    assert cells.load_cell("kanana2-30b-pretrain-8k-ep8").traffic == cell.traffic
    assert cell.traffic["overrides"] == {"model.optim.lr": 1.875e-05}
    for key in ("lr", "warmup_steps", "router_bias_update_rate", "renorm_eps", "head_dim",
                "tie_word_embeddings", "conv_history", "leaf_names", "initializer_range",
                "aux_loss", "rope"):
        assert key in cell.config["assumed"], key
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
    assert not {"moe_shared_ms_per_step", "moe_held_rows_share_p95", "mla_latent_ms_per_step"} & names
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED:
            assert CELL in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "tokens_per_s_per_chip" and m["workloads"] == [CELL]
            assert m["layer"] == ("kernels" if "roofline" in m["name"] else "train step")
    for name, component in zip(NEW_METRICS, ("short_conv", "conv_gate", "qk_norm")):
        spec = cells.load_layer_metric(name)
        assert (spec["reader"], spec["args"]) == ("inner_scope", {"component": component})
    spec = cells.load_layer_metric("conv_gate_roofline_pct")
    assert (spec["unit"], spec["reader"]) == ("%", "scope_roofline")
    assert spec["args"] == {"component": "conv_gate", "calls": "conv_gate_calls"}


def test_the_programs_scopes_are_the_ones_the_metrics_read():
    from neuronx_distributed_training_tpu.telemetry import spans

    from neuronx_distributed_training_tpu.ops import short_conv

    for name in NEW_METRICS:
        component = cells.load_layer_metric(name)["args"]["component"]
        assert component in spans.FAMILY_SCOPES["attention"]
    assert short_conv.WAY == "pallas"      # a kernel of its own: its roofline share is reported


def test_the_operations_add_up_part_by_part(cell):
    ops, model, seq = cell.operations, cell.model, cell.traffic["seq_length"]
    need = ops.train_flops_per_token(model, seq)
    parts = ("conv_projections", "conv_gate", "qkv_and_o", "scores", "dense_mlp", "router",
             "held_experts", "head")
    assert need["total"] == pytest.approx(sum(need[p] for p in parts), rel=1e-12)
    h, H, G, d = 2048, 32, 8, 64
    assert need["conv_projections"] == 6 * 6 * (h * 6144 + h * h)       # 6 convolution layers
    assert need["conv_gate"] == 6 * 3 * (2 * 3 * h + 2 * h)
    assert need["qkv_and_o"] == 2 * 6 * (h * (H + 2 * G) * d + H * d * h)
    assert need["scores"] == pytest.approx(2 * 3 * 2 * H * 2 * d * (seq + 1) / 2, rel=1e-12)
    assert need["held_slots_per_token"] == 4 * 8 / 64
    assert need["held_experts"] == 6 * 6 * 0.5 * 3 * h * 1536
    assert need["router"] == 6 * 6 * h * 64
    assert need["dense_mlp"] == 2 * 6 * 3 * h * 11776 and need["head"] == 6 * h * 8192
    assert 2.05e9 < need["total"] < 2.10e9
    # the distortion the header states: the dense MLPs are 42 % here; the new
    # operators and kernels mix every layer's tokens and are not most of it
    assert 0.41 < need["dense_mlp"] / need["total"] < 0.43
    assert 0.28 < (need["conv_projections"] + need["conv_gate"]) / need["total"] < 0.30
    assert 0.09 < need["scores"] / need["total"] < 0.11
    # the kernels: per visible pair and query head 2 x 2d, 2 x 3d, 2 x 4d
    calls = ops.kernel_calls(model, cell.traffic, 1)
    pairs = 2 * H * seq * (seq + 1) / 2
    assert calls["fwd"]["flops"] == pytest.approx(2 * pairs * 128, rel=1e-12)
    assert calls["dq"]["flops"] == pytest.approx(2 * pairs * 192, rel=1e-12)
    assert calls["dkv"]["flops"] == pytest.approx(2 * pairs * 256, rel=1e-12)
    # a traced step runs every kernel once a layer application: ``full`` keeps
    # the forward kernel's outputs (PR 40) and the rerun does not call it
    assert {k: v["calls"] for k, v in calls.items()} == {"fwd": 2, "dq": 2, "dkv": 2}
    assert ops.kernel_calls({**model, "activations_checkpoint_granularity": None},
                            cell.traffic, 1)["fwd"]["calls"] == 2
    q, kv, row = 2 * H * seq * d * 2, 2 * G * seq * d * 2, 2 * H * seq * 4
    assert calls["fwd"]["bytes"] == 2 * q + 2 * kv + row
    assert calls["dkv"]["bytes"] == 2 * q + 4 * kv + 2 * row
    peaks = flops.peaks_for("TPU v5 lite")
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "compute"
               for k in calls.values())
    # the convolution's middle: 16 KiB a token forward, 28 backward; the forward kernel
    # runs again where a layer is rematerialized: the runs of 2 and of 3 equal
    # layers (c c . c c c . c), not the last run of one
    gate = ops.conv_gate_calls(model, cell.traffic, 1)
    assert gate["fwd"]["bytes"] == 16384 * 16384 and gate["bwd"]["bytes"] == 16384 * 7 * 2048 * 2
    assert {k: v["calls"] for k, v in gate.items()} == {"fwd": 2 * 2 + 3 * 2 + 1, "bwd": 6}
    assert ops.conv_gate_calls({**model, "activations_checkpoint_granularity": None},
                               cell.traffic, 1)["fwd"]["calls"] == 6
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "memory"
               for k in gate.values())
    least = sum(k["calls"] * flops.roofline_seconds(k["flops"], k["bytes"], peaks)["seconds"]
                for k in gate.values())
    assert 0.0070 < least < 0.0071           # 7.05 ms a step at 819 GB/s


def test_the_scope_roofline_reader_reads_nothing_where_there_is_nothing_to_read(cell, tmp_path):
    """A program from before the scope (the parent's traced run of this cell
    does not exist; an accepted cell's operations have no ``conv_gate_calls``)
    and a run without a trace give None, not an error."""
    from benchmark.readers import scope_roofline

    peaks = flops.peaks_for("TPU v5 lite")
    ctx = {"log_dir": tmp_path, "trace": None, "cell": cell, "peaks": peaks, "data_parallel": 1}
    args = cells.load_layer_metric("conv_gate_roofline_pct")["args"]
    assert scope_roofline.read(ctx, **args) is None                        # untraced
    assert scope_roofline.read({**ctx, "trace": {"steps": 4}}, **args) is None   # no trace file
    kanana = cells.load_cell("kanana2-30b-pretrain-8k-ep8")
    assert not hasattr(kanana.operations, "conv_gate_calls")
    recorded = cells.ROOT / "benchmark/data"
    assert scope_roofline.read({**ctx, "log_dir": recorded, "trace": {"steps": 4},
                                "cell": kanana}, **args) is None


def test_the_programs_own_count_agrees_with_the_operations_file(cell):
    """``Family.flops_breakdown`` (what the trainer's MFU reads) and the
    benchmark's count are two writers' counts of the same forward pass."""
    from neuronx_distributed_training_tpu.models.family import resolve

    _, cfg = resolve({"model": cell.model})
    seq = cell.traffic["seq_length"]
    mine = sum(cfg.family.flops_breakdown(cfg, seq).values())
    assert 3 * mine == pytest.approx(
        cell.operations.train_flops_per_token(cell.model, seq)["total"], rel=1e-9)


def test_the_limits_name_the_routed_leaves_of_this_tree(cell):
    from benchmark.harness import check as checks

    limits = checks.limits_for(cell.config_name)
    # the group of its own is the selection bias's: it moves in whole steps of
    # 0.001 and takes no gradient; router and experts are held with every
    # other weight (PERF.md section 6)
    assert limits["routed_leaves"] == "mlp/router/bias"
    assert limits["grad1_routed_worst_leaf"] <= 1e-6
    assert set(limits) == {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf", "routed_leaves",
                           "grad1_routed_worst_leaf", "dparam_routed_worst_leaf"}
    # under a state left unchanged (1.0), with the more room above the readings
    assert limits["dparam_worst_leaf"] < 1.0 and limits["dparam_routed_worst_leaf"] < 1.0
    toy_file = json.loads((cells.ROOT / "tests/benchmark/toy_limits_lfm2.json").read_text())
    assert set(toy_file["limits"]) == set(limits) and toy_file["why"]
    names = cell.reference.leaf_names(cell.reference.init_params(
        toy(cell).model, __import__("jax").random.PRNGKey(0)))
    routed = [n for n in names if re.search(limits["routed_leaves"], n)]
    assert sorted(routed) == sorted(f"layers/{stack}/mlp/router/bias" for stack in SPARSE)
    assert "embed/embedding" in names and "embedding_norm/scale" in names
    assert not any("lm_head" in n or "final_norm" in n for n in names)      # the tied head


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
    for stack in SPARSE:
        assert f"layers/{stack}/mlp/router/bias" in result["compared"]["leaves"]["dparam"]


def test_traced_rehearsal_reads_the_counters_and_no_device_number():
    """``--trace 1`` off a TPU: the largest load is a counter of the program,
    read from the window's rows; the scopes' times and the roofline share are
    device numbers and are left out."""
    from test_benchmark_rehearsal import DEVICE_METRICS

    result = rehearse(trace=True)
    assert result["correct"] is True
    counters = {"moe_load_max_share_p95"}
    assert {"compile_s", "compiles_in_window"} | counters <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["moe_load_max_share_p95"]["value"] < 64 / 4
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
    """A step whose selection biases never move: the rule left out."""
    real = trainer.train_step

    def step(params, opt_state, batch, key):
        kept = {stack: params["layers"][stack]["mlp"]["router"]["bias"].copy()
                for stack in SPARSE}
        params, opt_state, metrics = real(params, opt_state, batch, key)
        for stack, bias in kept.items():
            params["layers"][stack]["mlp"]["router"]["bias"] = bias
        return params, opt_state, metrics

    trainer.train_step = step


def test_an_omitted_bias_update_is_not_correct(capsys):
    result = rehearse(tamper=no_bias_update)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert failed == {"dparam_routed_worst_leaf"}, failed
    leaves = result["compared"]["leaves"]["dparam"]
    for stack in SPARSE:
        assert leaves[f"layers/{stack}/mlp/router/bias"] == pytest.approx(1.0, abs=1e-6)
