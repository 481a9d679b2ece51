"""The ``laguna-s-2.1`` configuration's own files, beyond what the tests
parametrised over every configuration hold (header, modules, rehearsal, fp8
control): the cut is the one the header states, its operations count adds up
kind by kind, and its reader of one kind's flash kernels agrees with the
accepted reduction on a recorded trace and reads nothing where there is
nothing to read."""

import json
import time
import types

import pytest
from benchmark_toy import toy, toy_limits
from test_benchmark_scope_readers import BARE, SCOPED, ctx_for

from benchmark import flops, trace_reduce
from benchmark.harness import cell as cells
from benchmark.harness import drive
from benchmark.readers import inner_scope, kind_roofline

CELL = "laguna-s2.1-pretrain-ep32"
BENCH = cells.load_benchmark()
NEW_METRICS = ["attn_window_ms_per_step", "attn_full_ms_per_step", "moe_shared_ms_per_step",
               "flash_window_roofline_pct", "flash_full_roofline_pct",
               "moe_held_rows_share_p95"]


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def rehearse(**kw):
    """``test_benchmark_rehearsal.rehearse`` for this cell with a window of
    5 s: at toy widths it keeps the published counts (72 heads, a router 256
    wide), and a step of it can outlast 1 s on a loaded machine."""
    toy_cell = toy(cells.load_cell(CELL), seq=64)
    return drive.run_cell(toy_cell, seed=2**31 + 17, seconds=5.0,
                          t_process=time.perf_counter(), require_tpu=False,
                          limits=toy_limits(toy_cell), **{"trace": False, **kw})


def test_the_top_level_is_the_source_but_for_what_reduced_lists(cell):
    cfg = cell.config
    source = {k: v for k, v in cfg["published"].items() if "." not in k}
    cut = {k for k in source if cfg[k] != source[k]}
    assert cut == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["num_experts_held"] == [0, 8] and "num_experts_held" in cfg["reduced"]
    # the dotted keys of ``published`` repeat its own list and groups
    heads = dict(zip(source["layer_types"], source["num_attention_heads_per_layer"]))
    for t, n in heads.items():
        assert cfg["published"][f"num_attention_heads_per_layer.{t}"] == n
    assert all(n == heads[t] for t, n in zip(source["layer_types"],
                                             source["num_attention_heads_per_layer"]))
    for t, group in source["rope_parameters"].items():
        for k, v in group.items():
            assert cfg["published"][f"rope_parameters.{t}.{k}"] == v


def test_the_model_block_is_the_first_five_layers_and_every_width(cell):
    model, source = cell.model, cell.config["published"]
    n = model["num_hidden_layers"]
    assert n == cell.config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types"):
        assert model[key] == source[key][:n]
    # one whole period after the dense layer, every kind in its ratio
    assert model["layer_types"].count("sliding_attention") == 3
    assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert model["rope_parameters"] == source["rope_parameters"]
    assert model["num_experts"] == source["num_experts"] == 256       # the router's width
    assert model["num_experts_held"] == [0, 8]
    assert model["vocab_size"] * 8 == source["vocab_size"]
    # every number of the model block that is no count of what is held is a
    # published number under ``widths``
    widths = cell.config["widths"]
    flat = cells._flat(model)
    for key in ("moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "moe_routed_scaling_factor", "sliding_window",
                "num_attention_heads_per_layer.sliding_attention",
                "num_attention_heads_per_layer.full_attention",
                "rope_parameters.full_attention.rope_theta",
                "rope_parameters.sliding_attention.rope_theta",
                "rope_parameters.full_attention.factor",
                "rope_parameters.full_attention.attention_factor",
                "rope_parameters.full_attention.partial_rotary_factor"):
        assert flat[key] == source[widths[key]], key


def test_the_traffic_overrides_what_assumed_names(cell):
    assert cell.traffic["overrides"] == {"model.optim.lr": 1.33e-05}
    assert "lr" in cell.config["assumed"] and "warmup_steps" in cell.config["assumed"]
    assert cell.model["optim"]["sched"]["warmup_steps"] == 100
    assert cell.traffic["seq_length"] == 8192 == cell.config["published"][
        "rope_parameters.full_attention.original_max_position_embeddings"]


def test_the_cell_reports_the_rate_and_not_the_step_tail(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"attention_ms_per_step", "mlp_ms_per_step", "moe_ms_per_step",
            "flash_roofline_pct", "unscoped_device_pct"} <= names
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"


def test_the_operations_add_up_kind_by_kind(cell):
    ops, model, seq = cell.operations, cell.model, cell.traffic["seq_length"]
    need = ops.train_flops_per_token(model, seq)
    parts = ("projections", "scores", "dense_mlp", "router", "shared_expert",
             "held_experts", "head")
    assert need["total"] == pytest.approx(sum(need[p] for p in parts), rel=1e-12)
    h, d = 3072, 128
    # by hand: a window layer's projections and gate, and its capped scores
    window = 6 * (h * (72 + 16) * d + 72 * d * h + h * 72)
    full = 6 * (h * (48 + 16) * d + 48 * d * h + h * 48)
    assert need["projections"] == 3 * window + 2 * full
    keys_w = (512 * 513 / 2 + (seq - 512) * 512) / seq
    assert need["scores"] == pytest.approx(
        3 * 3 * 4 * 72 * d * keys_w + 2 * 3 * 4 * 48 * d * (seq + 1) / 2, rel=1e-12)
    assert need["held_slots_per_token"] == 10 * 8 / 256
    assert need["held_experts"] == 4 * 6 * 0.3125 * 3 * h * 1024
    assert need["shared_expert"] == 4 * 6 * 3 * h * 1024
    assert need["dense_mlp"] == 6 * 3 * h * 12288 and need["head"] == 6 * h * 12544
    assert 3.4e9 < need["total"] < 3.9e9
    # the two call shapes, and their sum as the accepted reader takes it
    by_type = ops.kernel_calls_by_type(model, cell.traffic, 1)
    assert {t: k["fwd"]["calls"] for t, k in by_type.items()} == {
        "full_attention": 4, "sliding_attention": 6}        # forward twice: recomputation
    assert {t: k["dkv"]["calls"] for t, k in by_type.items()} == {
        "full_attention": 2, "sliding_attention": 3}
    whole = ops.kernel_calls(model, cell.traffic, 1)
    for kind in ("fwd", "dq", "dkv"):
        for what in ("flops", "bytes"):
            assert whole[kind]["calls"] * whole[kind][what] == pytest.approx(
                sum(t[kind]["calls"] * t[kind][what] for t in by_type.values()), rel=1e-12)
    # a window call's scores are capped by the window, a full call's are causal
    w, f = by_type["sliding_attention"]["fwd"], by_type["full_attention"]["fwd"]
    assert w["flops"] == pytest.approx(4 * 72 * seq * keys_w * d, rel=1e-12)
    assert f["flops"] == pytest.approx(4 * 48 * seq * (seq + 1) / 2 * d, rel=1e-12)
    peaks = flops.peaks_for("TPU v5 lite")
    assert all(flops.roofline_seconds(k["flops"], k["bytes"], peaks)["bound"] == "compute"
               for t in by_type.values() for k in t.values())


def test_the_kinds_reader_agrees_with_the_accepted_reduction(tmp_path):
    """On the recorded Mistral trace every flash kernel lies under
    ``attention``: read by that scope, the kernels' time is what
    ``trace_reduce`` finds structurally."""
    reduced = trace_reduce.reduce(SCOPED)
    took = kind_roofline.kernel_seconds(SCOPED, "attention")
    assert took == pytest.approx(sum(reduced["flash_s"].values()) / reduced["steps"], rel=1e-6)
    # against a count of its calls it is the accepted share of the roofline
    mistral = cells.load_cell("mistral7b-pretrain-4k")
    ops = types.SimpleNamespace(kernel_calls_by_type=lambda m, t, dp: {
        "causal": mistral.operations.kernel_calls(m, t, dp)})
    ctx = ctx_for(tmp_path, SCOPED, peaks=flops.peaks_for("TPU v5 lite"), data_parallel=1,
                  cell=types.SimpleNamespace(operations=ops, model=mistral.model,
                                             traffic=mistral.traffic))
    share = kind_roofline.read(ctx, scope="attention", attention_type="causal")
    assert share == pytest.approx(51.1, abs=0.1) and share < 100


@pytest.mark.parametrize("scope", ["attn_window", "attn_full", "shared"])
def test_a_program_without_the_scope_reads_nothing(tmp_path, cell, scope):
    """The parent's program has none of the new scopes: the readers return
    None and do not raise, on a trace with scopes and on one without."""
    for k, fixture in enumerate((SCOPED, BARE)):
        assert kind_roofline.kernel_seconds(fixture, scope) is None
        assert inner_scope.reduce_component(fixture, scope) is None
        ctx = ctx_for(tmp_path / str(k), fixture, peaks=flops.peaks_for("TPU v5 lite"),
                      data_parallel=1, cell=cell)
        assert kind_roofline.read(ctx, scope=scope, attention_type="sliding_attention") is None
        assert inner_scope.read(ctx, component=scope) is None


def test_the_kinds_reader_reads_nothing_where_nothing_counts_kinds(tmp_path):
    ouro = cells.load_cell("ouro2.6b-pretrain-4k")     # its operations count no kinds
    ctx = ctx_for(tmp_path, SCOPED, peaks=flops.peaks_for("TPU v5 lite"), data_parallel=1,
                  cell=ouro)
    assert kind_roofline.read(ctx, scope="attention", attention_type="sliding_attention") is None
    off_chip = {"log_dir": tmp_path / "none", "trace": None, "cell": ouro, "peaks": None,
                "data_parallel": 1}
    assert kind_roofline.read(off_chip, scope="attn_window",
                              attention_type="sliding_attention") is None


def test_the_limits_name_the_routed_leaves_of_this_tree(cell):
    from benchmark.harness import check as checks

    limits = checks.limits_for(cell.config_name)
    assert limits["routed_leaves"] == "mlp/(router|experts)"
    assert set(limits) == {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf", "routed_leaves",
                           "grad1_routed_worst_leaf", "dparam_routed_worst_leaf"}
    # under a state left unchanged (1.0), with the more room above the readings
    assert limits["dparam_worst_leaf"] < 1.0 and limits["dparam_routed_worst_leaf"] < 1.0
    toy = json.loads((cells.ROOT / "tests/benchmark/toy_limits_laguna.json").read_text())
    assert set(toy["limits"]) == set(limits) and toy["why"]


# -- the cell end to end on the CPU, traced and with the timed path broken -------


def test_traced_rehearsal_reads_the_counters_and_no_device_number():
    """``--trace 1`` off a TPU: the held rows' share is a counter of the
    program, read from the window's rows; the scopes' times and both roofline
    shares are device numbers and are left out."""
    from test_benchmark_rehearsal import DEVICE_METRICS

    result = rehearse(trace=True)
    assert result["correct"] is True
    assert {"compile_s", "compiles_in_window", "moe_held_rows_share_p95"} <= set(
        result["metrics"])
    assert 0.0 < result["metrics"]["moe_held_rows_share_p95"]["value"] < 4.0
    assert not (DEVICE_METRICS | set(NEW_METRICS) - {"moe_held_rows_share_p95"}) & set(
        result["metrics"])
    assert "busy_s" not in result["device"] and list(result)[-1] == "compared"


def test_a_state_left_unchanged_is_not_correct(capsys):
    """Under the warm-up the first update runs at rate 0 and the next two at a
    hundredth and two hundredths of 1.33e-5: the parameters' change is small,
    and a step that returns its state unchanged still reads 1 against it."""
    from test_benchmark_rehearsal import stuck

    result = rehearse(tamper=stuck)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert {"dparam_worst_leaf", "dparam_routed_worst_leaf"} <= failed, failed
    assert result["compared"]["dparam_worst_leaf"] == pytest.approx(1.0, abs=1e-2)


def test_the_cell_rehearses_with_the_rate_and_no_step_tail(capsys):
    """The untraced line on the CPU at toy widths: ``correct``, the rate and
    ``setup_s``; no ``step_ms_p95`` (a step's time follows the rows one
    sequence sends to the held experts: PERF.md section 2), whose own sample is
    still printed."""
    result = rehearse()
    out = capsys.readouterr().out
    assert result["correct"] is True, "\n".join(
        l for l in out.splitlines() if l.startswith("check"))
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert "step time:" in out and "cut: num_experts_held" in out
    assert len(result["compared"]["limits"]) == 7
