"""The control of the correctness check, at a size a test run can hold: the
reference put in the program's place and computed in fp8 (the nearest
precision below the bf16 the configurations compute in) must come out as not
correct; the same comparison in float32 against itself is exact.  Plus what
``run.py`` refuses."""

import os
import shutil
import subprocess
import sys

import pytest
from benchmark_toy import toy, toy_limits

from benchmark.harness import cell as cells
from benchmark.harness import check as checks
from benchmark.harness import drive
from benchmark.harness import traffic as traffic_gen
from benchmark.harness.cell import ROOT

BENCH = cells.load_benchmark()
#: one cell per configuration
CELLS = {w["config"]: w["name"] for w in BENCH["workloads"]}


def three_steps(cell, seed, quant=None, fault=None):
    as_run = drive.merged_config(
        cell, drive.overrides_for(cell, seed, False, drive.WORK / "unused"))
    model = as_run["model"]
    tokens = drive.check_tokens(cell, model, seed)
    return cell.reference.run(model, model["optim"],
                              as_run["trainer"]["gradient_clip_val"],
                              fault(tokens) if fault else tokens, seed, quant=quant)


@pytest.mark.parametrize("config", sorted(CELLS))
def test_fp8_control_is_not_correct(config, capsys):
    cell = toy(cells.load_cell(CELLS[config]))
    limits = toy_limits(cell)
    for seed in (1, 2, 3):
        ref = three_steps(cell, seed)
        ok, compared = checks.compare(three_steps(cell, seed, "fp8"), ref, limits)
        assert not ok, compared
        assert compared["grad1_worst_leaf"] > limits["grad1_worst_leaf"]
    same, compared = checks.compare(ref, ref, limits)
    assert same and compared["grad1_worst_leaf"] == 0.0
    out = capsys.readouterr().out
    assert "FAILED" in out and " limit " in out


def test_half_the_batch_left_out_is_not_correct():
    """The fault ``tools/readings.py --fault half_batch`` plants in the
    reference put in the program's place (PERF.md: its chip readings are the
    upper readings of the four-chip cell's ``dparam`` limits): the first
    gradient and the parameters' change both see it, under the cell's warm-up
    too, whose first update runs at rate 0."""
    from benchmark.tools.readings import half_batch

    cell = toy(cells.load_cell("mixtral8x7b-pretrain-4k-ep4"))
    limits = toy_limits(cell)
    for seed in (1, 2, 3):
        ok, compared = checks.compare(three_steps(cell, seed, fault=half_batch),
                                      three_steps(cell, seed), limits)
        assert not ok
        for number in ("grad1_worst_leaf", "dparam_worst_leaf"):
            assert compared[number] > 10 * limits[number], (number, compared[number])
    rows = half_batch(drive.check_tokens(cell, cell.model, 1))[0][0]
    assert (rows[:2] == rows[2:]).all() and (rows[0] != rows[1]).any()


def test_traffic_is_a_function_of_the_seed_and_rows_differ():
    spec = {"kind": "uniform"}
    a = traffic_gen.token_rows(spec, 2**31 + 5, range(8), 64, 256)
    b = traffic_gen.token_rows(spec, 2**31 + 5, range(8), 64, 256)
    c = traffic_gen.token_rows(spec, 2**31 + 6, range(8), 64, 256)
    assert (a == b).all() and (a != c).any()
    assert len({row.tobytes() for row in a}) == 8 and a.min() >= 0 and a.max() < 256
    steps = traffic_gen.step_tokens(spec, 7, 1, seq_len=64, vocab=256,
                                    global_batch=4, micro_batches=2)
    assert steps.shape == (2, 2, 64)
    assert (steps.reshape(4, 64) == traffic_gen.token_rows(spec, 7, range(4, 8), 64, 256)).all()
    with pytest.raises(ValueError, match="unknown tokens.kind"):
        traffic_gen.token_rows({"kind": "zipf"}, 1, range(1), 8, 16)


def run_py(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "mistral7b-pretrain-4k", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_run_py_refuses_without_a_tpu():
    done = run_py(ROOT, *ARGS)
    assert done.returncode != 0 and "needs a TPU" in done.stderr
    assert '"correct"' not in done.stdout


def test_run_py_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = run_py(tmp_path, *ARGS)
    assert done.returncode != 0 and '"correct"' not in done.stdout
