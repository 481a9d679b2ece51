"""Every cell, end to end on the CPU mesh at toy widths: the same path
``run.py`` drives (``Trainer.from_config`` -> ``fit()``, the window, the
check against the plain reference), with the refusal to run off a TPU lifted
by the test.  No TPU topology is described anywhere in this file."""

import json
import time

import pytest
from benchmark_toy import toy, toy_limits

from benchmark.harness import cell as cells
from benchmark.harness import drive

BENCH = cells.load_benchmark()
DEVICE_METRICS = {"mfu_pct", "device_idle_pct", "flash_ms_per_step",
                  "flash_roofline_pct", "collective_ms_per_step"}


def rehearse(name, *, trace=False, seq=64, **kw):
    cell = toy(cells.load_cell(name), seq=seq)
    return drive.run_cell(
        cell, seed=2**31 + 17, seconds=1.0, trace=trace,
        t_process=time.perf_counter(), require_tpu=False,
        limits=toy_limits(cell), **kw)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_rehearses(name, capsys):
    result = rehearse(name)
    line = json.loads(json.dumps(result))      # what run.py prints last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    captured = capsys.readouterr()
    lines = captured.out.splitlines()
    assert line["correct"] is True, "\n".join(l for l in lines if l.startswith("check"))
    # each number compared beside its limit: last key of the line, last lines of stderr
    assert list(line)[-1] == "compared" and len(line["compared"]["limits"]) >= 5
    last = captured.err.splitlines()[-len(line["compared"]["limits"]):]
    assert all(l.startswith("check: ") and " limit " in l for l in last), last
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "step_ms_p95", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == name)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == chips
    # every cut and every number compared is printed, each beside its limit
    assert any(l.startswith("  cut: num_layers") for l in lines)
    assert sum(l.startswith("check: ") and " limit " in l for l in lines) >= 5


def test_traced_rehearsal_prints_no_device_number_from_a_cpu():
    # seq 256 > the toy window of 32: the sliding-window path
    result = rehearse("mistral7b-pretrain-32k", trace=True, seq=256)
    assert result["correct"] is True
    assert {"compile_s", "data_wait_ms_p95"} <= set(result["metrics"])
    assert not DEVICE_METRICS & set(result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-1] == "compared"


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys):
    import jax

    def tamper(trainer):
        real = trainer.train_step

        def stuck(params, opt_state, batch, key):
            keep = jax.tree_util.tree_map(lambda x: x.copy(), (params, opt_state))
            _, _, metrics = real(params, opt_state, batch, key)
            return keep[0], keep[1], metrics

        trainer.train_step = stuck

    result = rehearse("mistral7b-pretrain-4k", tamper=tamper)
    failed = [l for l in capsys.readouterr().out.splitlines() if "FAILED" in l]
    assert result["correct"] is False
    assert any("dparam_worst_leaf" in l for l in failed)
    assert any("grad1_worst_leaf" in l for l in failed)
