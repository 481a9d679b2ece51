"""``chip_smoke.py`` rehearsed on the CPU mesh at toy size (rehearsals 1 and 2
of the on-chip-measurement guide), so the script that proves the chip run does
not rot between chip runs.  What only the chip can show — compiled Mosaic
kernels, memory_stats — is stubbed here and nowhere else."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke as cs  # noqa: E402

TOY = {"model.hidden_size": 256, "model.intermediate_size": 512,
       "model.num_attention_heads": 2, "model.num_key_value_heads": 2,
       "model.vocab_size": 512, "data.seq_length": 128,
       "model.max_position_embeddings": 128}


@pytest.fixture
def rehearsal(monkeypatch, tmp_path):
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    for name in ("flash_attention", "flash_attention_with_lse"):
        real = getattr(fa, name)
        monkeypatch.setattr(fa, name, lambda *a, _real=real, **kw: _real(
            *a, **{**kw, "interpret": True}))
    real_check = cs.check

    def check(cond, what):
        if "tpu_custom_call" in what:  # interpret mode has no Mosaic call
            return
        real_check(cond, what)

    monkeypatch.setattr(cs, "check", check)
    monkeypatch.setattr(cs, "hbm_stats", lambda d: {
        "peak_bytes_in_use": 1, "bytes_limit": 2, "bytes_in_use": 1})
    monkeypatch.setattr(cs, "WORK", tmp_path)
    monkeypatch.setattr(cs, "EXTRA", TOY)


def test_refuses_to_start_without_a_tpu():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "needs a TPU" in r.stderr


def test_kernels_phase(rehearsal, monkeypatch):
    monkeypatch.setattr(cs, "KERNEL_HEADS", (4, 2, 128))
    monkeypatch.setattr(cs, "KERNEL_CASES", [
        (n, b, 256, m, sg, 128 if w else None, lse)
        for n, b, _s, m, sg, w, lse in cs.KERNEL_CASES])
    cs.phase_kernels(1234)


def test_train_phase_saves_verifies_and_resumes(rehearsal, monkeypatch,
                                                devices8):
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    # the CLI takes every device it finds; the chip run finds one
    real = Trainer.from_config.__func__
    monkeypatch.setattr(Trainer, "from_config", classmethod(
        lambda cls, cfg, **kw: real(cls, cfg, devices=devices8[:1], **kw)))
    cs.phase_train(1234)


def test_four_chip_legs_on_four_virtual_devices(rehearsal, devices8):
    cs.phase_four_chips(1234, devices8[:4])
