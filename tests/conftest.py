"""Test harness: force an 8-device virtual CPU platform BEFORE jax initializes.

This is the TPU ecosystem's "fake backend" (SURVEY.md §4): all TP/PP/CP/EP mesh
logic runs on 8 virtual CPU devices, so the full parallel stack is exercised
without hardware."""

import contextlib
import os
import signal
import time

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@contextlib.contextmanager
def case_limit(seconds: float, name: str = "the case"):
    """Fail what runs inside once it has taken ``seconds``, by ``name``: a hang
    then costs one case and not the whole run's limit.  The alarm's handler
    runs when Python next runs, so a compile is failed after it returns, not
    broken off.  On the way out whoever held the alarm before (a case's own
    handler, a limit around this one) gets it back with the time it had left."""
    def over(signum, frame):
        pytest.fail(f"{name} was still running after its limit of {seconds:g} s", pytrace=False)

    start = time.monotonic()
    handler = signal.signal(signal.SIGALRM, over)
    left, interval = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler)
        if left:
            signal.setitimer(signal.ITIMER_REAL,
                             max(left - (time.monotonic() - start), 1e-3), interval)


@pytest.fixture(autouse=True)
def _every_case_has_a_limit(request):
    # twice the longest sound case under the driver's load: the Kanana cell's
    # fp8 control reads 231-263 s there (50 s alone)
    with case_limit(600, request.node.nodeid):
        yield


#: the files that take minutes under the driver's command (junit seconds of a
#: whole run, PR 47: 615, 613, 298, 291, 289, 258, 239, 227, 221, 211), longest
#: first.  Handed out first, none of them is what the run ends on: left to
#: xdist's order, by the number of cases, ``benchmark/test_benchmark_control``
#: (11 cases) started last and ran on alone: 1 283 s where this order takes 998.
LONG_FILES = (
    "tests/test_tpu_compile.py", "tests/benchmark/test_benchmark_control.py",
    "tests/test_lfm2.py", "tests/test_laguna.py", "tests/test_flash_attention.py",
    "tests/test_nemotron_h.py", "tests/test_kanana.py", "tests/test_keye.py",
    "tests/benchmark/test_benchmark_rehearsal.py", "tests/test_pipeline_1f1b.py",
    "tests/test_graph_contract.py")


def pytest_configure(config):
    # ``--dist loadfile`` sorts the files by their number of cases unless told
    # not to (xdist's ``--no-loadscope-reorder``); then it keeps the collection's order
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONG_FILES)}
    items.sort(key=lambda item: rank.get(item.nodeid.split("::")[0], len(rank)))


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
