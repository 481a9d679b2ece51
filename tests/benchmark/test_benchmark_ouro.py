"""What the configuration ``ouro-2.6b`` brings, all of it new files and
entries (``benchmark/harness/cell.py``'s docstring).  Its operation count
against a count by hand, the reader of an inner scope against the trace
recorded on the chip, its file's statements, and its cell through the harness
at toy widths: a step that leaves its state unchanged, and float32, where
program and reference agree to rounding.  (The rehearsal and the fp8 control
are cases of ``test_benchmark_rehearsal.py`` and ``test_benchmark_control.py``
like every cell's, since ``benchmark_toy.toy_limits`` finds a configuration's
own toy limits.)

The toy runs are held to ``toy_limits_ouro.json`` beside this file, read
at toy widths through 8 layers x 4 passes: ``benchmark_toy.TOY_LIMITS`` was
read on one layer."""

import dataclasses
import json
import shutil
import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark import flops, trace_reduce
from benchmark.harness import cell as cells
from benchmark.harness import check as checks
from benchmark.harness import drive
from benchmark.harness.cell import HERE, ROOT
from benchmark.readers import scope_time
from benchmark.readers import inner_scope

CELL, CONFIG = "ouro2.6b-pretrain-4k", "ouro-2.6b"
SCOPED = HERE / "data" / "mistral7b-pretrain-4k-scoped.xplane.pb"
BENCH = cells.load_benchmark()
#: the accepted scope metrics whose ``workloads`` the cell joins
JOINS = {"attention_ms_per_step", "mlp_ms_per_step", "ce_head_ms_per_step",
         "optimizer_ms_per_step", "forward_ms_per_step", "backward_ms_per_step",
         "unscoped_device_pct"}
FLASH = {"flash_ms_per_step", "flash_roofline_pct"}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


# -- operations ---------------------------------------------------------------


def test_required_operations_against_a_count_by_hand(cell):
    tiny = {"hidden_size": 8, "intermediate_size": 16, "num_layers": 2,
            "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 4,
            "vocab_size": 32, "total_ut_steps": 3}
    got = cell.operations.train_flops_per_token(tiny, 6)
    layer = 8 * (2 + 2 * 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16      # qkv, o, gate_up + down
    assert layer == 640
    by_hand = {"stack": 6 * 3 * 2 * layer, "heads": 6 * 3 * 8 * 32, "gate": 6 * 3 * 8,
               "attention": 3 * 3 * 2 * 4 * 2 * 4 * 3.5}      # (6 + 1) / 2 mean keys
    assert {k: got[k] for k in by_hand} == by_hand
    assert got["total"] == sum(by_hand.values()) == 29808
    # the cell: ISSUE 27's figures, GFLOP a token
    need = cell.operations.train_flops_per_token(cell.model, cell.traffic["seq_length"])
    assert need["stack"] / 1e9 == pytest.approx(9.87, rel=0.005)
    assert need["heads"] / 1e9 == pytest.approx(2.42, rel=0.005)
    assert need["attention"] / 1e9 == pytest.approx(1.61, rel=0.005)
    assert need["total"] / 1e9 == pytest.approx(13.89, rel=0.002) and need["passes"] == 4
    # four passes of a dense decoder of these widths, and three more heads
    once = flops.train_flops_per_token(cell.model, cell.traffic["seq_length"])
    assert need["total"] == pytest.approx(
        4 * once["total"] + 6 * 4 * cell.model["hidden_size"], rel=1e-12)


def test_flash_calls_count_the_passes_and_the_rerun_forward(cell):
    calls = cell.operations.kernel_calls(cell.model, cell.traffic, 1)
    assert {k: v["calls"] for k, v in calls.items()} == {"fwd": 64, "dq": 32, "dkv": 32}
    visible = 16 * 4096 * 2048.5                               # heads x queries x mean keys
    assert calls["fwd"]["flops"] == 2 * 2 * visible * 128
    assert calls["dkv"]["flops"] == 2 * 4 * visible * 128
    q = 16 * 4096 * 128 * 2
    assert calls["fwd"]["bytes"] == 4 * q + 16 * 4096 * 4      # q k v o (16 kv heads) + lse
    # no recomputation: the forward kernel runs once an application
    plain = cell.operations.kernel_calls(
        {**cell.model, "activations_checkpoint_granularity": None},
        {**cell.traffic, "global_batch_size": 4, "micro_batches": 2}, 2)
    assert {k: v["calls"] for k, v in plain.items()} == {"fwd": 64, "dq": 64, "dkv": 64}
    assert plain["fwd"]["flops"] == calls["fwd"]["flops"]      # one row a chip either way


# -- the reader of an inner scope ---------------------------------------------


def test_inner_scope_reads_a_component_wherever_it_sits():
    by_path = scope_time.reduce_scopes(SCOPED)["by_path"]
    for component, paths in {"flash_fwd": ["attention/flash_fwd"],
                             "adamw": ["optimizer/adamw"], "clip": ["optimizer/clip"],
                             "mlp": ["mlp"]}.items():
        assert inner_scope.reduce_component(SCOPED, component) == pytest.approx(
            sum(by_path[p] for p in paths), rel=1e-9), component
    # a top-level scope holds its inner scopes' time too
    assert inner_scope.reduce_component(SCOPED, "attention") == pytest.approx(
        scope_time.scope_seconds({"by_path": by_path}, "attention"), rel=1e-9)
    # a program from before the gate names it nowhere: nothing to read, no error
    assert inner_scope.reduce_component(SCOPED, "exit_gate") is None


def test_inner_scope_reader_returns_nothing_without_raising(tmp_path):
    run = tmp_path / "trace" / "plugins" / "profile" / "t0"
    run.mkdir(parents=True)
    shutil.copy(SCOPED, run / "host.xplane.pb")
    ctx = {"log_dir": tmp_path, "trace": trace_reduce.reduce(SCOPED)}
    assert inner_scope.read(ctx, component="exit_gate") is None
    assert inner_scope.read(ctx, component="flash_dq") == pytest.approx(7.8666164, rel=1e-6)
    assert inner_scope.read({**ctx, "trace": None}, component="flash_dq") is None
    assert inner_scope.read({"log_dir": tmp_path / "absent", "trace": ctx["trace"]},
                            component="flash_dq") is None


# -- the files -----------------------------------------------------------------


def test_the_cell_joins_the_accepted_scope_metrics(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (w["config"], w["chips"], w["traffic"]) == (CONFIG, 1, "pretrain-4k-gbs1")
    # the cell is in the lists it joins, in the two flash metrics' (every cell
    # that runs the kernels) and in its own metric's, and in no other
    listed = {m["name"]: m["workloads"] for m in BENCH["per_layer"] if "workloads" in m}
    assert listed["exit_gate_ms_per_step"] == [CELL]
    assert {name for name, cells_ in listed.items() if CELL in cells_} \
        == JOINS | FLASH | {"exit_gate_ms_per_step"}
    # it reports those and every list-less metric
    listless = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} == (
        listless | JOINS | FLASH | {"exit_gate_ms_per_step"})
    spec = cells.load_layer_metric("exit_gate_ms_per_step")
    assert spec["reader"] == "inner_scope" and spec["args"] == {"component": "exit_gate"}


def test_the_cells_limits_are_its_own_file_and_the_table_is_as_it_was():
    table = json.loads((HERE / "limits.json").read_text())
    assert set(table) == {"mistral-7b", "mixtral-8x7b"}       # a name in both is an error
    own = checks.limits_for(CONFIG)
    assert own == json.loads((HERE / "limits" / f"{CONFIG}.json").read_text())
    assert own["routed_leaves"] == "exit_gate"
    # the depth is cut under the source's own spelling: the ``hidden`` in it
    # is no width, and a width beside it is still refused
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert "num_hidden_layers" in entry["reduced"] and "num_layers" not in entry["reduced"]
    assert cells.header_faults(cells.load_config_file(BENCH, CONFIG),
                               entry["reduced"] + ["hidden_size"]) \
        == ["reduced names a width: hidden_size", "reduced cuts hidden_size, which is no count"]


def test_the_configuration_states_its_cut_and_what_it_assumes(cell):
    cfg, model = cell.config, cell.model
    published = cfg["published"]
    assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # every width as published; only depth differs
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
                "rms_norm_eps", "total_ut_steps"):
        assert model[key] == published[key], key
        assert cfg["widths"][key] == key
    assert (published["num_hidden_layers"], model["num_layers"]) == (48, 8)
    assert model["tie_word_embeddings"] is published["tie_word_embeddings"] is False
    assert set(cfg["reduced"]) == {"num_hidden_layers", "global_batch_size",
                                   "max_steps", "warmup_steps"}
    assert "trainer_config.model.num_layers" in cfg["reduced"]["num_hidden_layers"]
    # the source's keys stand at the top level, number for number, but the
    # depth, which stands there as it is run
    for key, value in published.items():
        assert cfg[key] == (8 if key == "num_hidden_layers" else value), key
    assert {"exit_entropy_beta", "carried_state", "norm_leaf_names", "initializer_range",
            "exit_gate_init", "recomputation", "gate_only_stage"} <= set(cfg["assumed"])
    assert model["exit_entropy_beta"] == 0.1 and model["initializer_range"] == 0.02
    assert model["activations_checkpoint_granularity"] == "full"
    assert "pipeline" in cfg["deployment"] and "8 of 48" in cfg["deployment"]
    assert cfg["modules"] == {"reference": "ouro", "operations": "ouro"}
    t = cell.traffic
    assert (t["seq_length"], t["micro_batch_size"], t["global_batch_size"],
            t["micro_batches"], t["check_steps"], t["warmup_steps"], t["trace_steps"]) == (
        4096, 1, 1, 1, 3, 2, 4)
    assert t["overrides"] == {"model.optim.sched.warmup_steps": 0}
    limits = checks.limits_for(CONFIG, cell.root)
    assert {"loss_gap", "grad1_worst_leaf", "dparam_worst_leaf"} <= set(limits)


# -- the cell through the harness, at toy widths -------------------------------


def rehearse(cell, name, **kw):
    # a name of its own: the run directory is the cell's, and these runs may
    # stand beside each other in several workers
    return drive.run_cell(
        dataclasses.replace(toy(cell), name=f"{CELL}-{name}"), trace=False,
        t_process=time.perf_counter(), require_tpu=False,
        **{"seed": 2**31 + 17, "seconds": 1.0, "limits": toy_limits(cell), **kw})


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell, capsys):
    """The limits on the loss and on the parameters' change are three times
    the sound program's largest reading (two updates at the peak rate swing
    the exit distribution more than fp8 does): they still hold the update."""
    import jax

    def tamper(trainer):
        real = trainer.train_step

        def stuck(params, opt_state, batch, key):
            keep = jax.tree_util.tree_map(lambda x: x.copy(), (params, opt_state))
            _, _, metrics = real(params, opt_state, batch, key)
            return keep[0], keep[1], metrics

        trainer.train_step = stuck

    # held to the cell's own limits, the loosest it is ever held to
    own = checks.limits_for(CONFIG, cell.root)
    result = rehearse(cell, "stuck", tamper=tamper, limits=own)
    failed = [l for l in capsys.readouterr().out.splitlines() if "FAILED" in l]
    assert result["correct"] is False
    assert any("dparam_worst_leaf" in l for l in failed)
    assert any("grad1_worst_leaf" in l for l in failed)


# -- the cell through the harness, in float32 ---------------------------------


def test_program_and_reference_agree_to_rounding_in_float32(cell, capsys):
    """Three steps through ``Trainer.from_config(cfg).fit()`` with the
    program's own float32 regime switched on: the losses, every leaf's first
    gradient and every leaf's change, the gate and the four norms of a layer
    among them, meet the float32 reference two hundred times closer than the
    bfloat16 cell's toy limits ask."""
    tight = {"loss_gap": 5e-6, "grad1_worst_leaf": 2e-5, "dparam_worst_leaf": 2e-5}
    result = rehearse(cell, "float32", seed=2**31 + 27, seconds=0.5, limits=tight,
                      overrides={"precision.type": "fp32"})
    lines = capsys.readouterr().out.splitlines()
    assert result["correct"] is True, "\n".join(l for l in lines if l.startswith("check"))
    leaves = result["compared"]["leaves"]["grad1"]
    assert {"exit_gate/w", "exit_gate/bias", "layers/input_norm_2/scale",
            "layers/post_attn_norm_2/scale", "final_norm/scale"} <= set(leaves)
    need = next(l for l in lines if l.startswith("required operations per token"))
    assert "benchmark.operations.ouro" in need and "passes 4" in need
