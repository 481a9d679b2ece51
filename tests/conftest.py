"""Test harness: force an 8-device virtual CPU platform BEFORE jax initializes.

This is the TPU ecosystem's "fake backend" (SURVEY.md §4): all TP/PP/CP/EP mesh
logic runs on 8 virtual CPU devices, so the full parallel stack is exercised
without hardware."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def cpu_mesh(devices8):
    """Default 8-device mesh: dp=4 x tp=2."""
    from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(tensor_model_parallel_size=2), devices=devices8)


def lower_in_mesh(mesh, fn, *args):
    """Lower + compile ``fn(*args)`` INSIDE ``mesh``'s context — the shared
    guard for every test that inspects a compiled train/loss graph.

    Lowering outside ``with mesh, shd.use_mesh(mesh)`` silently drops every
    ``shd.constrain`` in the traced program (constrain no-ops without an
    active mesh), so a FLOPs/memory gate would pin a graph WITHOUT the
    sharding constraints it claims to measure (round-4 advisor finding on
    tests/test_pp_flops_parity.py).  The assert makes that mistake loud."""
    import jax as _jax

    from neuronx_distributed_training_tpu.parallel import sharding as shd

    with mesh, shd.use_mesh(mesh):
        assert shd.active_mesh() is mesh, (
            "lower_in_mesh: no active mesh at lower time — shd.constrain "
            "would silently no-op in the compiled graph"
        )
        lowered = (fn.lower(*args) if hasattr(fn, "lower")
                   else _jax.jit(fn).lower(*args))
        return lowered.compile()


def ragged_right_pad_mask(b, s, valid_lens):
    """[b, s] int32 attention_mask with row i real for its first valid_lens[i]
    positions (the HF right-padding convention) — shared by the masked
    flash/ring/ulysses parity tests."""
    import numpy as np
    import jax.numpy as jnp

    m = np.zeros((b, s), dtype=np.int32)
    for i, n in enumerate(valid_lens):
        m[i, :n] = 1
    return jnp.asarray(m)
