"""Every cell, end to end on the CPU mesh at toy widths: the same path
``run.py`` drives (``Trainer.from_config`` -> ``fit()``, the window, the
check against the plain reference), with the refusal to run off a TPU lifted
by the test.  No TPU topology is described anywhere in this file."""

import json
import re
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
    # the accepted three; the tail of single steps wherever a step's time is the
    # program's alone: in the four-chip cell it follows the tokens' routing and
    # is no end-to-end metric there (PERF.md section 2)
    cell = cells.load_cell(name)
    tail = {"step_ms_p95"} if cell.chips == 1 else set()
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"} | tail
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == cell.chips
    # every cut and every number compared is printed, each beside its limit
    assert any(re.match(r"  cut: num_(hidden_)?layers", l) for l in lines)
    assert sum(l.startswith("check: ") and " limit " in l for l in lines) >= 5


@pytest.mark.parametrize("name, seq, reads", [
    # seq 256 > the toy window of 32: the sliding-window path
    ("mistral7b-pretrain-32k", 256, set()),
    # the closing trace logs a row of its own at a step of the window (the
    # all-to-alls' rates): a step's rows are read as one; and the regime of
    # the expert exchange is read from the window's rows, off a TPU too
    ("mixtral8x7b-pretrain-4k-ep4", 64,
     {"moe_weights_way_step_pct", "moe_recv_share_max_p95", "window_step_ms_p95"})])
def test_traced_rehearsal_prints_no_device_number_from_a_cpu(name, seq, reads):
    result = rehearse(name, trace=True, seq=seq)
    assert result["correct"] is True
    assert {"compile_s", "data_wait_ms_p95", "compiles_in_window",
            "log_metrics_ms_p95"} | reads <= set(result["metrics"])
    if reads:
        assert result["metrics"]["moe_weights_way_step_pct"]["value"] == 0.0
        assert 1.0 <= result["metrics"]["moe_recv_share_max_p95"]["value"] < 2.0
    assert not DEVICE_METRICS & set(result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-1] == "compared"


def stuck(trainer):
    """A step that returns its state unchanged."""
    import jax

    real = trainer.train_step

    def step(params, opt_state, batch, key):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), (params, opt_state))
        _, _, metrics = real(params, opt_state, batch, key)
        return keep[0], keep[1], metrics

    trainer.train_step = step


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest: the second
    half of every step's rows replaced by the first, at the same shapes."""
    import jax
    import jax.numpy as jnp

    real = trainer.train_step

    def twice(a):                            # [micro, rows, seq]
        half = a.shape[1] // 2
        assert half, "the cell's batch has one row"
        return jax.device_put(
            jnp.concatenate([a[:, :half], a[:, :half]], axis=1), a.sharding)

    trainer.train_step = lambda params, opt_state, batch, key: real(
        params, opt_state, jax.tree_util.tree_map(twice, batch), key)


@pytest.mark.parametrize("name, tamper, fails", [
    ("mistral7b-pretrain-4k", stuck, {"dparam_worst_leaf", "grad1_worst_leaf"}),
    # the four-chip cell under its warm-up, whose first update runs at rate 0:
    # the state left unchanged still reads 1 against the parameters' change
    ("mixtral8x7b-pretrain-4k-ep4", stuck,
     {"dparam_worst_leaf", "dparam_routed_worst_leaf"}),
    ("mixtral8x7b-pretrain-4k-ep4", half_batch,
     {"grad1_worst_leaf", "grad1_routed_worst_leaf", "dparam_worst_leaf"})])
def test_the_timed_path_broken_underneath_is_not_correct(name, tamper, fails, capsys):
    result = rehearse(name, tamper=tamper)
    failed = {l.split()[1] for l in capsys.readouterr().out.splitlines()
              if l.startswith("check: ") and "FAILED" in l}
    assert result["correct"] is False
    assert fails <= failed, failed
