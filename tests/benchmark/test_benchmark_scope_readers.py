"""The readers that turn the program's named scopes, span annotations and
compile counter into per-layer metrics, against a trace recorded on the chip
in PR 24 with the scopes in (``mistral7b-pretrain-4k``, one v5e, six traced
steps) and against PR 23's trace, which has none."""

import shutil
import types

import pytest

from benchmark import trace_reduce, xplane_meta
from benchmark.harness.cell import HERE
from benchmark.readers import compile_events, idle_spans, scope_time

SCOPED = HERE / "data" / "mistral7b-pretrain-4k-scoped.xplane.pb"
BARE = HERE / "data" / "mistral7b-pretrain-4k.xplane.pb"
STEPS = 5
#: self seconds per step by scope path, as the fixture reads
BY_PATH = {
    "attention": 0.026283184, "attention/flash_fwd": 0.0065078166,
    "attention/flash_dq": 0.0078666164, "attention/flash_dkv": 0.0102044636,
    "ce_head": 0.0779657366, "embed": 0.0130755322, "grad_accum": 4.268e-07,
    "mlp": 0.0999448484, "optimizer/adamw": 0.0197094458,
    "optimizer/clip": 0.0024505454, "unscoped": 0.0075560958,
}


@pytest.fixture(scope="module")
def scoped():
    assert SCOPED.stat().st_size <= 2 * 2**20
    return scope_time.reduce_scopes(SCOPED)


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(SCOPED)


def ctx_for(tmp_path, fixture, **more):
    """What ``harness/layers.py`` hands a reader, around a recorded trace."""
    run = tmp_path / "trace" / "plugins" / "profile" / "t0"
    run.mkdir(parents=True)
    shutil.copy(fixture, run / "host.xplane.pb")
    return {"log_dir": tmp_path, "trace": trace_reduce.reduce(fixture), **more}


# -- the metadata reader ----------------------------------------------------


@pytest.mark.parametrize("fixture", [SCOPED, BARE], ids=["scoped", "bare"])
def test_tf_op_of_every_timed_operation_but_copies(fixture):
    tf = xplane_meta.tf_ops(fixture)
    names = {n for n, _, _ in trace_reduce.load(fixture)["chips"][0]}
    assert len(tf) == 249 and set(tf) <= names
    steps = [v for v in tf.values() if v.startswith("jit(train_step)/")]
    assert len(steps) >= 240 and all(v.startswith("jit(") for v in tf.values())
    # what has none (copies, a convert, the while itself) is under 1 % of
    # the device's busy time
    sc = scope_time.reduce_scopes(fixture)
    bare = sum(s for (op, _), s in sc["unscoped_ops"].items() if op == "(no tf_op)")
    assert 0 < bare < 0.01 * sc["busy"]


def test_wire_reader_on_bytes_made_by_hand(tmp_path):
    def varint(n):
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    def field(num, wire, payload):
        head = bytes([num << 3 | wire])
        return head + (varint(len(payload)) + payload if wire == 2 else payload)

    def entry(key, message):
        return field(1, 0, bytes([key])) + field(2, 2, message)

    stat_meta = field(1, 0, b"\x07") + field(2, 2, b"tf_op")
    other_meta = field(1, 0, b"\x09") + field(2, 2, b"flops")
    stats = (field(5, 2, field(1, 0, b"\x09") + field(3, 0, b"\x2a"))
             + field(5, 2, field(1, 0, b"\x07") + field(5, 2, b"jit(f)/attention/mul:")))
    event = field(1, 0, b"\x01") + field(2, 2, b"%fusion.1 = f32[] fusion()") + stats
    bare_event = field(1, 0, b"\x02") + field(2, 2, b"%copy.1 = f32[] copy()")

    def plane(name):
        return (field(2, 2, name) + field(4, 2, entry(1, event))
                + field(4, 2, entry(2, bare_event))
                + field(5, 2, entry(7, stat_meta)) + field(5, 2, entry(9, other_meta)))

    space = field(1, 2, plane(b"/host:CPU")) + field(1, 2, plane(b"/device:TPU:0"))
    assert list(xplane_meta._fields(field(3, 0, b"\xac\x02"))) == [(3, 0, 300)]
    path = tmp_path / "made_by_hand.xplane.pb"
    path.write_bytes(space)
    assert xplane_meta.tf_ops(path) == {
        "%fusion.1 = f32[] fusion()": "jit(f)/attention/mul:"}


# -- the name stack ---------------------------------------------------------

STACK = "jit(train_step)/while/body/closed_call/"


@pytest.mark.parametrize("tf_op, path, phase, remat", [
    (STACK + "jvp()/while/body/closed_call/attention/flash_fwd/flash_fwd/pallas_call:",
     "attention/flash_fwd", "forward", False),
    (STACK + "transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_dq/pallas_call:",
     "attention/flash_dq", "backward", True),
    (STACK + "transpose(jvp(embed))/jit(_take)/scatter-add:", "embed", "backward", False),
    (STACK + "jvp(ce_head)/reduce_max:", "ce_head", "forward", False),
    (STACK + "jvp()/while/body/closed_call/moe/dispatch/jit(clip)/clamp:",
     "moe/dispatch", "forward", False),          # jit(clip) is not the scope clip
    ("jit(train_step)/optimizer/adamw/zero1_bucket_ag/all-gather:",
     "optimizer/adamw/zero1_bucket_ag", "neither", False),
    ("jit(train_step)/optimizer/clip/mul:", "optimizer/clip", "neither", False),
    ("jit(train_step)/clip/mul:", None, "neither", False),   # inner scope alone
    (STACK + "transpose(jvp())/remat(mlp)/dot_general:", "mlp", "backward", True),
    ("moe/router", "moe/router", "neither", False),
    ("ragged-dot-none:", "moe/experts", "neither", False),   # XLA's own name for it
    (STACK + "jvp()/transpose:", None, "forward", False),    # the primitive transpose
    ("", None, "neither", False),
])
def test_scope_path_phase_and_remat(tf_op, path, phase, remat):
    assert scope_time.scope_path(tf_op) == path
    assert scope_time.phase(tf_op) == phase
    assert scope_time.under_remat(tf_op) is remat


def test_the_yardsticks_table_is_the_programs():
    from neuronx_distributed_training_tpu.telemetry.spans import DEVICE_SCOPES

    assert scope_time.SCOPES == DEVICE_SCOPES


# -- device time by scope ---------------------------------------------------


@pytest.mark.parametrize("where", list(BY_PATH))
def test_self_seconds_by_scope_path(scoped, where):
    assert set(scoped["by_path"]) == set(BY_PATH)
    assert scoped["by_path"][where] == pytest.approx(BY_PATH[where], abs=1e-10)


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_scope_finder_and_flash_kind_agree(scoped, reduced, kind):
    # by name here, by the structure of the HLO line there: the same events
    by_scope = scoped["by_path"][f"attention/flash_{kind}"] * STEPS
    assert by_scope == pytest.approx(reduced["flash_s"][kind], rel=1e-9)


def test_scopes_and_unscoped_make_busy_self_time(scoped, reduced):
    assert sum(scoped["by_path"].values()) == pytest.approx(scoped["busy"], rel=1e-12)
    assert sum(scoped["by_phase"].values()) == pytest.approx(scoped["busy"], rel=1e-12)
    assert sum(scoped["by_row"].values()) == pytest.approx(scoped["busy"], rel=1e-12)
    # self times tile the union of the operations' intervals
    assert scoped["busy"] * STEPS == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert scoped["busy"] * STEPS == pytest.approx(
        sum(reduced["op_seconds"].values()), rel=1e-9)
    assert scoped["collective"] == {}           # one chip


def test_forward_backward_and_the_part_under_checkpoint(scoped):
    ph = scoped["by_phase"]
    assert ph["forward"] == pytest.approx(0.0822535394, abs=1e-10)
    assert ph["backward"] == pytest.approx(0.1624402854, abs=1e-10)
    assert ph["neither"] == pytest.approx(0.0268708868, abs=1e-10)
    assert 0 < scoped["remat_backward"] < ph["backward"]
    assert scoped["remat_backward"] == pytest.approx(0.1009182514, abs=1e-10)
    # optimizer and accumulation are outside the differentiated function
    outside = sum(v for k, v in scoped["by_path"].items()
                  if k.startswith(("optimizer", "grad_accum")))
    assert outside <= ph["neither"]


@pytest.mark.parametrize("args, value", [
    ({"what": "scope_ms_per_step", "scope": "attention"}, 50.8620806),
    ({"what": "scope_ms_per_step", "scope": "mlp"}, 99.9448484),
    ({"what": "scope_ms_per_step", "scope": "ce_head"}, 77.9657366),
    ({"what": "scope_ms_per_step", "scope": "optimizer"}, 22.1599912),
    ({"what": "scope_ms_per_step", "scope": "moe"}, None),   # a dense step
    ({"what": "forward_ms_per_step"}, 82.2535394),
    ({"what": "backward_ms_per_step"}, 162.4402854),
    ({"what": "unscoped_pct"}, 2.7824292),
])
def test_reader_on_the_scoped_trace(tmp_path, capsys, args, value):
    got = scope_time.read(ctx_for(tmp_path, SCOPED), **args)
    assert got == (None if value is None else pytest.approx(value, abs=1e-6))
    said = capsys.readouterr().out
    assert "scopes: attention/flash_dkv custom-call 10.204 ms a step" in said
    if args.get("scope") == "optimizer":
        assert "scopes: optimizer/adamw 19.709 ms a step" in said
    if args["what"] == "backward_ms_per_step":
        assert "100.918 of it under checkpoint" in said
    if args["what"] == "unscoped_pct":
        assert "scopes: unscoped fusion" in said and "reduce_precision" in said


@pytest.mark.parametrize("args, value", [
    ({"what": "scope_ms_per_step", "scope": "attention"}, None),
    ({"what": "scope_ms_per_step", "scope": "optimizer"}, None),
    ({"what": "unscoped_pct"}, None),
    ({"what": "forward_ms_per_step"}, 82.253),      # jvp( needs no scope
    ({"what": "backward_ms_per_step"}, 162.453),
])
def test_reader_on_a_program_without_scopes(tmp_path, args, value):
    got = scope_time.read(ctx_for(tmp_path, BARE), **args)
    assert got == (None if value is None else pytest.approx(value, abs=1e-3))


def test_readers_read_nothing_off_the_chip(tmp_path):
    # off a TPU the harness leaves ctx["trace"] None; and a CPU trace has no
    # device plane to read
    import jax
    import jax.numpy as jnp

    ctx = {"log_dir": tmp_path, "trace": None}
    assert scope_time.read(dict(ctx), what="unscoped_pct") is None
    assert idle_spans.read(dict(ctx)) is None
    jax.profiler.start_trace(str(tmp_path / "trace"))
    with jax.profiler.StepTraceAnnotation("train", step_num=0):
        jnp.ones((8, 8)).sum().block_until_ready()
    with jax.profiler.StepTraceAnnotation("train", step_num=1):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(tmp_path / "trace")
    assert scope_time.reduce_scopes(xplane) is None
    assert idle_spans.reduce_idle(xplane) is None
    with pytest.raises(ValueError, match="unknown what"):
        scope_time.read(ctx_for(tmp_path / "x", SCOPED), what="nonsense")


# -- idle time by the program's spans ---------------------------------------


def test_idle_by_span_and_unattributed_make_idle(reduced):
    idle = idle_spans.reduce_idle(SCOPED)
    assert idle["idle"] == pytest.approx(0.0103923546, abs=1e-10)
    assert idle["idle"] * STEPS == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    # the loop's spans do not nest, so they tile the attributed part
    assert sum(idle["by_span"].values()) + idle["unattributed"] == pytest.approx(
        idle["idle"], rel=1e-9)
    assert idle["by_span"]["host_sync"] == pytest.approx(0.0037853354, abs=1e-10)
    assert idle["by_span"]["log_metrics"] == pytest.approx(0.001884694, abs=1e-10)
    assert idle["by_span"]["data_wait"] == pytest.approx(0.0005522894, abs=1e-10)
    assert idle["unattributed"] == pytest.approx(0.0041700346, abs=1e-10)


def test_idle_reader_and_a_program_without_span_annotations(tmp_path, capsys):
    assert idle_spans.read(ctx_for(tmp_path / "a", SCOPED)) == pytest.approx(
        40.1259845, abs=1e-6)
    said = capsys.readouterr().out
    assert "idle: 3.785 ms a step under host_sync" in said
    assert "idle: 4.170 of 10.392 ms a step under no span" in said
    assert idle_spans.reduce_idle(BARE) is None
    assert idle_spans.read(ctx_for(tmp_path / "b", BARE)) is None


def test_overlap_of_gaps_and_spans():
    gaps = [(0, 10), (20, 30), (40, 50)]
    assert idle_spans._overlap(gaps, [(5, 25), (28, 29), (45, 60)]) == 5 + 5 + 1 + 5
    assert idle_spans._overlap(gaps, []) == 0
    assert idle_spans._overlap([], [(0, 5)]) == 0
    assert idle_spans._overlap([(0, 100)], [(10, 20), (30, 40)]) == 20


# -- compiles in the window -------------------------------------------------


@pytest.mark.parametrize("events, value", [
    ([{"step": 0, "seconds": 2.6}, {"step": 3, "seconds": 0.1}], 0.0),
    ([{"step": 0, "seconds": 2.6}, {"step": 5, "seconds": 0.2},
      {"step": 40, "seconds": 5.7}], 2.0),
    ([{"step": 113, "seconds": 0.1}], 0.0),          # after the window's last step
    ([], 0.0),
    (None, None),                                    # a program without the counter
])
def test_compiles_in_the_window(events, value, capsys):
    cell = types.SimpleNamespace(traffic={"check_steps": 3, "warmup_steps": 2})
    summary = {} if events is None else {"compile_events": events}
    ctx = {"cell": cell, "summary": summary,
           "rows": [{"step": s} for s in range(6, 113)]}
    assert compile_events.read(ctx) == value
    if value:
        assert "compile in the window: step 40, 5.7 s" in capsys.readouterr().out
    assert compile_events.read({**ctx, "rows": []}) is None
