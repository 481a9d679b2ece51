"""The ``nemotron-3-nano-30b-a3b`` configuration's own files, beyond what the
tests parametrised over every configuration hold (header, modules, fp8
control): the top level is the catalog's config but for what ``reduced``
lists, the cut is the one the header states, its operations count adds up part
by part and counts the forward kernel's calls as a traced step runs them (once
a layer application) and the scan's as required work, the cell rehearses on
the CPU as it is, and an omitted bias update and a state left unchanged are
caught."""

import dataclasses
import json
import re
import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark import flops
from benchmark.harness import cell as cells
from benchmark.harness import drive

CELL = "nemotron3-nano-pretrain-8k-ep16"
BENCH = cells.load_benchmark()
NEW_METRICS = ["mamba_ms_per_step", "ssd_scan_ms_per_step", "mamba_conv_ms_per_step",
               "gated_norm_ms_per_step", "ssd_scan_roofline_pct"]
JOINED = ["attention_ms_per_step", "moe_ms_per_step", "ce_head_ms_per_step",
          "optimizer_ms_per_step", "forward_ms_per_step", "backward_ms_per_step",
          "unscoped_device_pct", "flash_ms_per_step", "flash_roofline_pct",
          "moe_router_ms_per_step", "moe_load_max_share_p95"]
BIAS = "layers/moe/mlp/router/bias"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module", autouse=True)
def one_reference_run(cell):
    """The rehearsals below hold a program, whole or tampered with, against
    the same reference run (one cell, one seed: the reference knows nothing of
    the program): it is run once."""
    real, kept = cell.reference.run, {}

    def run(model, optim, clip, tokens, seed, **kw):
        if kw.get("quant") or kw.get("left_out"):
            return real(model, optim, clip, tokens, seed, **kw)
        key = json.dumps([model, optim, clip, seed], sort_keys=True, default=str)
        if key not in kept:
            kept[key] = real(model, optim, clip, tokens, seed, **kw)
        return kept[key]

    cell.reference.run = run
    yield
    cell.reference.run = real


def rehearse(**kw):
    """``test_benchmark_rehearsal.rehearse`` for this cell with a window of
    5 s: at toy widths it keeps the published counts (a router 128 wide,
    experts of 1856, 64 Mamba heads of 64 with a state of 128), and a step of
    it can outlast 1 s on a loaded machine."""
    # a name of its own: the run directory is the cell's, and
    # test_benchmark_rehearsal.py rehearses the cell in another process
    toy_cell = dataclasses.replace(toy(cells.load_cell(CELL), seq=64), name=f"{CELL}-own")
    return drive.run_cell(toy_cell, seed=2**31 + 17, t_process=time.perf_counter(),
                          require_tpu=False, limits=toy_limits(toy_cell),
                          **{"trace": False, "seconds": 5.0, **kw})


def test_the_top_level_is_the_source_but_for_what_reduced_lists(cell):
    cfg = cell.config
    source = cfg["published"]
    assert all(k in cfg for k in source)
    cut = {k for k in source if cfg[k] != source[k]}
    assert cut == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["num_experts_held"] == [0, 8] and "num_experts_held" in cfg["reduced"]
    assert cfg["hybrid_override_pattern"] == source["hybrid_override_pattern"] == PATTERN
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*"), len(PATTERN)) == (
        23, 23, 6, 52)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size",
                                   "global_batch_size", "max_steps"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "nemotron-3-nano-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]


def test_the_model_block_is_the_patterns_first_nine_layers_at_every_width(cell):
    model, source = cell.model, cell.config["published"]
    assert model["num_hidden_layers"] == cell.config["num_hidden_layers"] == 9
    assert model["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert model["n_routed_experts"] == source["n_routed_experts"] == 128   # the router's width
    assert model["num_experts_held"] == [0, 8]
    assert model["vocab_size"] * 8 == source["vocab_size"]
    widths = cell.config["widths"]
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
                "conv_kernel", "chunk_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "layer_norm_epsilon", "max_position_embeddings"):
        assert model[key] == source[widths[key]], key
    assert (model["mamba_num_heads"], model["mamba_head_dim"], model["ssm_state_size"],
            model["n_groups"], model["conv_kernel"], model["chunk_size"]) == (64, 64, 128, 8, 4, 128)
    assert (model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]) == (
        32, 2, 128)
    assert (model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"],
            model["num_experts_per_tok"], model["routed_scaling_factor"]) == (1856, 3712, 6, 2.5)
    assert widths["num_experts_held"] == "n_routed_experts"
    for key in ("use_conv_bias", "mamba_proj_bias", "attention_bias", "mlp_bias", "norm_topk_prob",
                "mamba_hidden_act", "mlp_hidden_act", "tie_word_embeddings", "time_step_min",
                "time_step_max", "time_step_floor", "rescale_prenorm_residual", "n_group",
                "topk_group"):
        assert model[key] == source[key], key
    assert "rope_theta" not in model and "partial_rotary_factor" not in model   # unread
    deployment = cell.config["deployment"]
    assert "16 chips" in deployment and "12 %" in deployment and "1/16 of the rows" in deployment
    assert "45%" in cell.why and "1.6% published" in cell.why and cell.chips == 1


def test_the_traffic_is_kananas_file_and_overrides_what_assumed_names(cell):
    assert cell.traffic_name == "pretrain-8k-mb2"
    assert cells.load_cell("kanana2-30b-pretrain-8k-ep8").traffic == cell.traffic
    assert cell.traffic["overrides"] == {"model.optim.lr": 1.875e-05}
    for key in ("attention_positions", "router_bias_update_rate", "aux_loss", "renorm_eps",
                "time_step_limit", "mamba_init", "initializer_range", "leaf_names", "lr",
                "warmup_steps"):
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
    # membership only: a later cell joins these lists, and this cell others,
    # without an edit here
    assert set(NEW_METRICS) | set(JOINED) <= names
    assert "mlp_ms_per_step" not in names      # no layer of this family opens ``mlp``
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED:
            assert CELL in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "tokens_per_s_per_chip"
            assert m["layer"] == ("kernels" if "roofline" in m["name"] else "train step")
    from neuronx_distributed_training_tpu.telemetry import spans
    for name, component in zip(NEW_METRICS, ("mamba", "ssd_scan", "mamba_conv", "gated_norm")):
        spec = cells.load_layer_metric(name)
        assert (spec["reader"], spec["args"]) == ("inner_scope", {"component": component})
        assert component in spans.FAMILY_SCOPES["attention"]
    spec = cells.load_layer_metric("ssd_scan_roofline_pct")
    assert (spec["unit"], spec["reader"]) == ("%", "scope_roofline")
    assert spec["args"] == {"component": "ssd_scan", "calls": "ssd_calls"}


def test_the_operations_add_up_part_by_part(cell):
    ops, model, seq = cell.operations, cell.model, cell.traffic["seq_length"]
    need = ops.train_flops_per_token(model, seq)
    parts = ("mamba_projections", "mamba_conv", "ssd_scan", "qkv_and_o", "scores", "router",
             "shared_expert", "held_experts", "head")
    assert need["total"] == pytest.approx(sum(need[p] for p in parts), rel=1e-12)
    h = 2688
    assert need["mamba_projections"] == 4 * 6 * (h * (4096 + 6144 + 64) + 4096 * h)
    assert need["mamba_conv"] == 4 * 3 * 2 * 4 * 6144
    assert need["ssd_scan"] == 4 * 3 * (6 * 4096 * 128 + 2 * 4096)
    assert need["qkv_and_o"] == 6 * (h * (32 + 4) * 128 + 4096 * h)
    assert need["scores"] == pytest.approx(3 * 2 * 32 * 2 * 128 * (seq + 1) / 2, rel=1e-12)
    assert need["held_slots_per_token"] == 6 * 8 / 128
    assert need["held_experts"] == 4 * 6 * 0.375 * 2 * h * 1856         # two matrices: no gate
    assert need["shared_expert"] == 4 * 6 * 2 * h * 3712
    assert need["router"] == 4 * 6 * h * 128 and need["head"] == 6 * h * 16384
    assert 2.14e9 < need["total"] < 2.16e9
    # the shares the header states: the Mamba-2 layers are the largest, the head 12 %
    mamba = need["mamba_projections"] + need["mamba_conv"] + need["ssd_scan"]
    assert 0.44 < mamba / need["total"] < 0.46
    assert 0.26 < (need["router"] + need["shared_expert"] + need["held_experts"]
                   ) / need["total"] < 0.28
    assert 0.15 < (need["qkv_and_o"] + need["scores"]) / need["total"] < 0.17
    assert 0.12 < need["head"] / need["total"] < 0.13
    # the kernels: per visible pair and query head 2 x 2d, 2 x 3d, 2 x 4d at d 128
    calls = ops.kernel_calls(model, cell.traffic, 1)
    pairs = 2 * 32 * seq * (seq + 1) / 2
    assert calls["fwd"]["flops"] == pytest.approx(2 * pairs * 256, rel=1e-12)
    assert calls["dq"]["flops"] == pytest.approx(2 * pairs * 384, rel=1e-12)
    assert calls["dkv"]["flops"] == pytest.approx(2 * pairs * 512, rel=1e-12)
    # a traced step runs every kernel once a layer application: ``full`` keeps
    # the forward kernel's outputs (PR 40) and the rerun does not call it
    assert {k: v["calls"] for k, v in calls.items()} == {"fwd": 1, "dq": 1, "dkv": 1}
    q, kv, row = 2 * 32 * seq * 128 * 2, 2 * 2 * seq * 128 * 2, 2 * 32 * seq * 4
    assert calls["fwd"]["bytes"] == 2 * q + 2 * kv + row
    assert calls["dkv"]["bytes"] == 2 * q + 4 * kv + 2 * row
    peaks = flops.peaks_for("TPU v5 lite")
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "compute"
               for k in calls.values())
    # the scan's REQUIRED work: 20 608 B a token forward (x and y 8 KiB each, B
    # and C 2 KiB each, dt 128 B), one forward and one backward a layer a step
    # whatever reruns; memory-bound by its bytes
    scan = ops.ssd_calls(model, cell.traffic, 1)
    assert scan["fwd"]["bytes"] == 16384 * 20608
    assert scan["bwd"]["bytes"] == 16384 * (2 * (4096 + 2048 + 64) + 4096) * 2
    assert scan["fwd"]["flops"] == 16384 * (6 * 4096 * 128 + 2 * 4096)
    assert {k: v["calls"] for k, v in scan.items()} == {"fwd": 4, "bwd": 4}
    assert ops.ssd_calls({**model, "activations_checkpoint_granularity": None},
                         cell.traffic, 1)["fwd"]["calls"] == 4
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "memory"
               for k in scan.values())
    least = sum(k["calls"] * flops.roofline_seconds(k["flops"], k["bytes"], peaks)["seconds"]
                for k in scan.values())
    assert 0.0042 < least < 0.0044           # 4.29 ms a step at 819 GB/s
    from neuronx_distributed_training_tpu.ops import ssd
    assert ssd.bytes_per_token(64, 64, 128, 8) == 20608


def test_the_programs_own_count_agrees_with_the_operations_file(cell):
    """``Family.flops_breakdown`` (what the trainer's MFU reads) and the
    benchmark's count are two writers' counts of the same forward pass."""
    from neuronx_distributed_training_tpu.models.family import resolve

    _, cfg = resolve({"model": cell.model})
    seq = cell.traffic["seq_length"]
    mine = sum(cfg.family.flops_breakdown(cfg, seq).values())
    need = cell.operations.train_flops_per_token(cell.model, seq)
    # the program leaves the skip's ``D x`` out of its count
    assert 3 * mine == pytest.approx(need["total"] - 4 * 3 * 2 * 4096, rel=1e-9)


def test_the_limits_name_the_routed_leaves_of_this_tree(cell):
    from benchmark.harness import check as checks

    limits = checks.limits_for(cell.config_name)
    # the group of its own holds the leaves whose numbers do not tell a lower
    # precision from the stated one: the router (its weight's gradient hangs
    # on which experts a token chose; the selection bias moves in whole steps
    # of 0.001 and takes no gradient) and the one attention layer's qkv/w
    # (PERF.md section 6); every other leaf, the experts among them, is held
    # by the tight limit, under every control seed's reading
    assert limits["routed_leaves"] == "mlp/router|attention/attn/qkv/w"
    assert limits["grad1_worst_leaf"] < limits["grad1_routed_worst_leaf"] <= 1.4e-2
    assert set(limits) == {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf", "routed_leaves",
                           "grad1_routed_worst_leaf", "dparam_routed_worst_leaf"}
    # under a state left unchanged (1.0), with the more room above the readings
    assert limits["dparam_worst_leaf"] < 1.0 and limits["dparam_routed_worst_leaf"] < 1.0
    toy_file = json.loads((cells.ROOT / "tests/benchmark/toy_limits_nemotron_h.json").read_text())
    assert set(toy_file["limits"]) == set(limits) and toy_file["why"]
    names = cell.reference.leaf_names(cell.reference.init_params(
        toy(cell).model, __import__("jax").random.PRNGKey(0)))
    assert [n for n in names if re.search(limits["routed_leaves"], n)] == [
        "layers/attention/attn/qkv/w", BIAS, "layers/moe/mlp/router/w"]
    assert {"embed/embedding", "final_norm/scale", "lm_head/w",           # the untied head
            "layers/mamba/mixer/head_scales/A_log", "layers/mamba/mixer/conv/bias",
            "layers/moe/mlp/shared/gate_up/w", "layers/attention/attn/qkv/w"} <= set(names)


# -- the cell end to end on the CPU, and with the timed path broken ---------------


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
    assert BIAS in result["compared"]["leaves"]["dparam"]


def test_a_state_left_unchanged_is_not_correct(capsys):
    """Under the warm-up the weights' change is small and the bias's is three
    steps of 0.001: a step that returns its state unchanged reads 1 against
    both."""
    from test_benchmark_rehearsal import stuck

    result = rehearse(tamper=stuck, seconds=3.0)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert {"dparam_worst_leaf", "dparam_routed_worst_leaf"} <= failed, failed
    assert result["compared"]["dparam_routed_worst_leaf"] == pytest.approx(1.0, abs=1e-2)


def no_bias_update(trainer):
    """A step whose selection bias never moves: the rule left out."""
    real = trainer.train_step

    def step(params, opt_state, batch, key):
        kept = params["layers"]["moe"]["mlp"]["router"]["bias"].copy()
        params, opt_state, metrics = real(params, opt_state, batch, key)
        params["layers"]["moe"]["mlp"]["router"]["bias"] = kept
        return params, opt_state, metrics

    trainer.train_step = step


def test_an_omitted_bias_update_is_not_correct(capsys):
    result = rehearse(tamper=no_bias_update, seconds=3.0)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert failed == {"dparam_routed_worst_leaf"}, failed
    assert result["compared"]["leaves"]["dparam"][BIAS] == pytest.approx(1.0, abs=1e-6)
