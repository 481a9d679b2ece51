"""``trace_reduce`` against a trace recorded on the chip in PR 23:
``mistral7b-pretrain-4k``, one v5e, six traced steps (five whole periods)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce
from benchmark.harness.cell import HERE

FIXTURE = HERE / "data" / "mistral7b-pretrain-4k.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    assert FIXTURE.stat().st_size <= 2 * 2**20
    return trace_reduce.reduce(FIXTURE)


def test_window_busy_and_idle(reduced):
    assert reduced["chips"] == 1 and reduced["steps"] == 5
    assert reduced["window_s"] == pytest.approx(1.412649501, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(1.357553331, abs=1e-9)
    idle = 100 * (1 - reduced["busy_s"] / reduced["window_s"])
    assert idle == pytest.approx(3.900201, abs=1e-5)


def test_flash_and_collective_time(reduced):
    assert reduced["flash_s"] == pytest.approx(
        {"fwd": 0.03254048, "dq": 0.039332537, "dkv": 0.051019947}, abs=1e-9)
    assert reduced["collective_s"] == 0.0      # one chip: none


def test_roofline_share_through_the_cells_own_operations(reduced):
    # the recorded trace of PR 23 reads 51.1 % (PERF.md); the reader takes the
    # kernels' operations, bytes and calls from ``cell.operations``
    from benchmark import flops
    from benchmark.harness import cell as cells
    from benchmark.readers import roofline

    ctx = {"trace": reduced, "peaks": flops.peaks_for("TPU v5 lite"),
           "cell": cells.load_cell("mistral7b-pretrain-4k"), "data_parallel": 1}
    assert roofline.read(ctx) == pytest.approx(51.10520325837087, rel=1e-12)
    assert roofline.read({**ctx, "peaks": None}) is None


def test_op_times_are_self_times(reduced):
    # the while loop over micro-batches holds nearly every operation; its own
    # time must not count them twice
    assert sum(reduced["op_seconds"].values()) <= reduced["busy_s"] * 1.001
    top = max(reduced["op_seconds"], key=reduced["op_seconds"].get)
    assert "while" not in top and len(top) < 80


def test_idle_gaps_name_what_the_host_did(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10 and gaps[0][1] == pytest.approx(0.008624376, abs=1e-9)
    assert {g[0] for g in gaps} <= {"host_sync", "data_wait", "log_metrics",
                                    "dispatch", "other"}
    assert gaps[0][0] == "log_metrics"


def test_flash_kind_reads_structure_not_names():
    q = "bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)}"
    kv = "bf16[1,8,4096,128]{3,2,1,0:T(8,128)(2,1)}"
    lse = "f32[1,32,4096,8]{3,2,1,0:T(8,128)}"
    args = f"custom-call({q} %a, {kv} %b, {kv} %c), custom_call_target=\"tpu_custom_call\""
    assert trace_reduce.flash_kind(f"%x.1 = ({q}, {lse}) {args}") == "fwd"
    assert trace_reduce.flash_kind(f"%anything = {q} {args}") == "dq"
    assert trace_reduce.flash_kind(f"%y = ({kv}, {kv}) {args}") == "dkv"
    assert trace_reduce.flash_kind(
        "%ragged-dot = bf16[8192,4096]{1,0} custom-call(bf16[8192,14336]{1,0} %a)") is None
    assert trace_reduce.flash_kind(f"%f = {q} fusion({q} %a)") is None
    assert trace_reduce.short_name(f"%fusion.231 = {q} fusion({q} %a)") == "fusion.231 fusion"


def test_no_device_plane_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(Path(tmp_path))
    assert xplane is not None and trace_reduce.reduce(xplane) is None
