"""What a run loads, and when (PERF.md section 6, PR 48): importing the
trainer loop and building an ``ExpManager`` with the default sinks loads none
of ``orbax``, ``torch`` and ``tensorflow``; the first ``Checkpointer`` loads
orbax, which in a run that checkpoints is ``startup/checkpointer``'s.

A pytest worker has all three loaded already, so each of the two scripts runs
in an interpreter of its own, once a module."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HEAVY = ("orbax", "torch", "tensorflow")
REPO = Path(__file__).resolve().parents[1]


def _run(script: str, tmp_path: Path) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    done = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.splitlines()[-1])


LOADS = r"""
import json, sys
import neuronx_distributed_training_tpu.trainer.loop
from neuronx_distributed_training_tpu.checkpoint import (
    CheckpointConfig, Checkpointer)
from neuronx_distributed_training_tpu.telemetry.spans import STARTUP
from neuronx_distributed_training_tpu.trainer.exp_manager import ExpManager

def roots():
    return sorted({m.split(".")[0] for m in sys.modules})

tmp = sys.argv[1]
exp = ExpManager(exp_dir=tmp, name="loads", log_every_n_steps=1)
for step in (1, 2, 3):
    exp.log_metrics(step, {"loss": 1.0 / step, "time/dispatch": 0.25})
exp.close()
out = {"before": roots(), "imports_before": dict(STARTUP.imports_s),
       "tb": [p.name for p in (exp.log_dir / "tb").iterdir()]}
Checkpointer(CheckpointConfig(dir=tmp + "/ck")).close()
out.update(after=roots(), has_checkpoint="orbax.checkpoint" in sys.modules,
           imports_after=dict(STARTUP.imports_s))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def loads(tmp_path_factory):
    return _run(LOADS, tmp_path_factory.mktemp("loads"))


@pytest.mark.parametrize("module", HEAVY)
def test_the_loop_and_the_default_sinks_load_none_of(loads, module):
    assert module not in loads["before"]


def test_the_tensorboard_import_is_timed_under_its_own_name(loads):
    assert set(loads["imports_before"]) == {
        "tensorboard.summary.writer.event_file_writer"}
    assert len(loads["tb"]) == 1
    assert loads["tb"][0].startswith("events.out.tfevents.")


def test_the_first_checkpointer_loads_orbax_and_times_it(loads):
    assert loads["has_checkpoint"] and "orbax" in loads["after"]
    assert loads["imports_after"]["orbax.checkpoint"] > 0.0


@pytest.mark.parametrize("module", ("torch", "tensorflow"))
def test_a_checkpointer_loads_neither(loads, module):
    assert module not in loads["after"]


# -- a run that checkpoints loads orbax where it builds its Checkpointer -------

CHECKPOINTS = r"""
import json, sys
from pathlib import Path
from neuronx_distributed_training_tpu.config.loader import load_config
from neuronx_distributed_training_tpu.trainer.loop import Trainer

assert "orbax" not in sys.modules
cfg = load_config({
    "name": "ck", "model_source": "hf", "seed": 7,
    "trainer": {"max_steps": 2, "log_every_n_steps": 1},
    "exp_manager": {"exp_dir": sys.argv[1] + "/exp", "log_files": False},
    "data": {"global_batch_size": 8, "micro_batch_size": 1,
             "seq_length": 32, "synthetic": True},
    "model": {"vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
              "num_layers": 1, "num_attention_heads": 2,
              "num_key_value_heads": 2, "max_position_embeddings": 32,
              "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
    "precision": {"type": "mixed_precision"},
})
trainer = Trainer.from_config(cfg, enable_checkpointing=True)
trainer.fit()
summary = json.loads(
    (Path(trainer.exp.log_dir) / "run_summary.json").read_text())
print(json.dumps({"startup": summary["startup"],
                  "saved": trainer.checkpointer.latest_step(),
                  "heavy": [m for m in ("torch", "tensorflow")
                            if m in sys.modules]}))
"""


@pytest.fixture(scope="module")
def checkpoints(tmp_path_factory):
    return _run(CHECKPOINTS, tmp_path_factory.mktemp("checkpoints"))


def test_a_run_that_checkpoints_pays_the_import_in_its_checkpointer_phase(
        checkpoints):
    section = checkpoints["startup"]
    orbax = section["imports_s"]["orbax.checkpoint"]
    assert 0.0 < orbax <= section["seconds"]["checkpointer"] + 1e-3
    # and not with the loop's imports
    assert section["seconds"]["imports"] < orbax


def test_the_run_trains_and_saves_and_loads_neither_torch_nor_tensorflow(
        checkpoints):
    assert checkpoints["saved"] == 2
    assert checkpoints["heavy"] == []
