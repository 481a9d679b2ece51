"""Where the persistent compilation cache lands (utils/compile_cache.py):
``JAX_COMPILATION_CACHE_DIR`` set -> no directory set in code; unset -> one
fixed in-checkout path; ``--compilation-cache`` -> explicit override."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from neuronx_distributed_training_tpu.utils import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    worker's real cache setting must not move under other tests)."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_env_set_means_no_directory_set_in_code(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    cc.configure_compilation_cache()
    assert "jax_compilation_cache_dir" not in config_updates


def test_env_unset_means_one_fixed_in_checkout_path(monkeypatch,
                                                    config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cc.configure_compilation_cache()
    assert config_updates["jax_compilation_cache_dir"] == str(
        REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_explicit_override_wins(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    cc.configure_compilation_cache("/explicit")
    assert config_updates["jax_compilation_cache_dir"] == "/explicit"


def test_two_nxdt_train_runs_share_the_env_cache(tmp_path):
    """Two ``nxdt-train`` runs in one tree with the cache placed from outside:
    the first fills ``JAX_COMPILATION_CACHE_DIR`` and the second reuses its
    entry of the step (adds none)."""
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": f"{REPO}{os.pathsep}{os.environ.get('PYTHONPATH', '')}"}

    def run(name):
        subprocess.run(
            [sys.executable, "-m", "neuronx_distributed_training_tpu.trainer.cli",
             "--config", str(REPO / "examples/conf/tiny_smoke_config.yaml"),
             "--set", f"exp_manager.exp_dir={tmp_path / name}",
             "--set", "trainer.max_steps=2",
             "--set", "exp_manager.create_checkpoint_callback=false"],
            env=env, check=True, capture_output=True, timeout=600)
        # the step's entries only: a compile becomes an entry when it took 1 s
        # (``configure_compilation_cache``), which for the small programs
        # around the step depends on how loaded the machine is
        return {p.name for p in cache.iterdir()
                if "train_step" in p.name and "-atime" not in p.name}

    first = run("a")
    assert first
    assert run("b") == first
