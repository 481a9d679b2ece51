"""The TensorBoard sink (trainer/exp_manager.py::_ScalarEvents): a boundary's
scalars as one ``Event`` of a ``tfevents`` file under ``<log_dir>/tb``,
written without ``torch`` and read back here with tensorboard's own loader."""

import builtins
import json
import logging

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_training_tpu.trainer.exp_manager import ExpManager

#: the kinds of value the loop hands ``log_metrics``
VALUES = {
    "float": 2.75,
    "int": 3,
    "numpy_float32": np.float32(0.1),
    "jax_0d": jnp.asarray(1e-4, jnp.float32),
}


def _exp(tmp_path, **kw):
    return ExpManager(exp_dir=tmp_path, name="tb", log_every_n_steps=1,
                      log_files=False, **kw)


def _events(exp):
    """``(step, {tag: value})`` of every summary event under ``tb/``."""
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader,
    )

    files = sorted((exp.log_dir / "tb").iterdir())
    assert len(files) == 1 and files[0].name.startswith("events.out.tfevents.")
    events = list(EventFileLoader(str(files[0])).Load())
    assert events[0].file_version == "brain.Event:2"
    out = []
    for e in events[1:]:
        # the loader hands a ``simple_value`` over as a float32 tensor
        out.append((e.step, {
            v.tag: (v.tensor.float_val[0] if v.HasField("tensor")
                    else v.simple_value)
            for v in e.summary.value}))
    return out


@pytest.mark.parametrize("kind", VALUES)
@pytest.mark.parametrize("tag", ["loss", "time/log_metrics", "moe/aux/l0"])
def test_a_scalar_comes_back_under_its_tag_and_step(tmp_path, kind, tag):
    exp = _exp(tmp_path)
    exp.log_metrics(7, {tag: VALUES[kind], "lr": 0.5})
    exp.log_metrics(8, {tag: VALUES[kind]})
    exp.close()
    (s7, v7), (s8, v8) = _events(exp)
    assert (s7, s8) == (7, 8)
    assert set(v7) == {tag, "lr"} and set(v8) == {tag}   # one event a step
    want = np.float32(float(VALUES[kind]))
    assert np.float32(v7[tag]) == want and np.float32(v8[tag]) == want
    assert v7["lr"] == 0.5
    rows = [json.loads(x) for x in
            (exp.log_dir / "metrics.jsonl").read_text().splitlines()]
    assert [r["step"] for r in rows] == [7, 8]


def test_close_twice_is_harmless_and_a_later_row_still_reaches_the_file(tmp_path):
    exp = _exp(tmp_path)
    exp.log_metrics(1, {"loss": 1.0})
    exp.close()
    exp.close()
    exp.log_metrics(2, {"loss": 0.5})      # the sink is gone, the file is not
    assert [s for s, _ in _events(exp)] == [1]
    assert len((exp.log_dir / "metrics.jsonl").read_text().splitlines()) == 2


def test_no_sink_asked_for_makes_no_directory(tmp_path):
    exp = _exp(tmp_path, create_tensorboard_logger=False)
    exp.log_metrics(1, {"loss": 1.0})
    exp.close()
    assert not (exp.log_dir / "tb").exists()


def test_a_failed_import_warns_and_leaves_the_jsonl_written(
        tmp_path, monkeypatch, caplog):
    real = builtins.__import__

    def no_tensorboard(name, *a, **kw):
        if name.split(".")[0] == "tensorboard":
            raise ImportError("No module named 'tensorboard'")
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_tensorboard)
    with caplog.at_level(logging.WARNING):
        exp = _exp(tmp_path)
    assert any("TensorBoard logger unavailable" in r.getMessage()
               for r in caplog.records)
    exp.log_metrics(1, {"loss": 1.25})
    exp.close()
    row, = (exp.log_dir / "metrics.jsonl").read_text().splitlines()
    assert json.loads(row)["loss"] == 1.25
