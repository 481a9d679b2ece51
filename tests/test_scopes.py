"""The step's named scopes (``telemetry.spans.DEVICE_SCOPES``), the loop's
spans on the profiler's clock, and the backend-compile counter: what a trace
reduction and ``run_summary.json`` are promised, checked on the CPU mesh at
toy widths.  The v5e compile of the same promise is in
``tests/test_tpu_compile.py``."""

import collections
import contextlib
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_training_tpu.telemetry.spans import (
    DEVICE_SCOPES,
    SpanTimer,
)

EX = Path(__file__).resolve().parents[1] / "examples" / "conf"
#: toy widths the flash kernel tiles at (interpret mode on the CPU): head
#: size and blocks of one lane width; two micro-batches so the accumulation
#: carry exists
TOY = {
    "model.hidden_size": 256, "model.num_attention_heads": 2,
    "model.num_key_value_heads": 1, "model.intermediate_size": 256,
    "model.num_layers": 2, "data.seq_length": 128,
    "model.max_position_embeddings": 128,
    "model.fusions.flash_attention": True,
    "model.fusions.flash_block_q": 128, "model.fusions.flash_block_kv": 128,
    "distributed_strategy.tensor_model_parallel_size": 1,
    "distributed_strategy.sequence_parallel": False,
    "data.micro_batch_size": 1, "data.global_batch_size": 4,
}
CASES = {
    "llama": ("hf_llama_7B_config.yaml", {}),
    "mixtral": ("hf_mixtral_8x7b_config.yaml", {
        "distributed_strategy.expert_model_parallel_size": 2}),
    # the looped stack: a head and an exit gate at the end of every pass
    "ouro": ("hf_ouro_2_6b_config.yaml", {}),
}


def compile_toy_step(name):
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
        shrink_overrides,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    config, extra = CASES[name]
    path = str(EX / config)
    cfg = load_config(path, {"data.synthetic": True})
    cfg = load_config(path, {**shrink_overrides(cfg), "data.synthetic": True,
                             **TOY, **extra})
    asm = assemble_step_program(cfg, devices=jax.devices()[:2],
                                build_data=False)
    return lower_step_program(asm)[1]


def op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def opcode_census(compiled):
    """Instructions by opcode over the compiled module's text."""
    return collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][a-z0-9\-]*)\(",
            compiled.as_text(), re.M))


def memory_totals(compiled):
    ma = compiled.memory_analysis()
    return {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def has_scope(names, scope, *, wrapped_by=None, inside=None):
    """Some op_name holds ``scope`` as a component, bare or wrapped; with
    ``wrapped_by`` the name stack also holds that transform, with ``inside``
    that scope before it."""
    for n in names:
        parts = [re.sub(r"^(?:(?:jvp|transpose|checkpoint|remat)\()+|\)+$",
                        "", p) for p in n.split("/")]
        if scope not in parts:
            continue
        if wrapped_by and wrapped_by not in n:
            continue
        if inside and inside not in parts[:parts.index(scope)]:
            continue
        return True
    return False


@pytest.fixture(scope="module")
def no_persistent_cache():
    """The persistent compilation cache keys a program without its metadata,
    so a compile with the scopes taken out would be answered by the entry of
    the compile with them in (names and all), whenever an earlier test of the
    worker turned the cache on and the first compile took long enough to be
    kept: the way ``test_scopes_are_metadata_the_program_is_the_same`` failed
    under the driver's six workers and passed alone (ROADMAP, PR 25)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    previous = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", previous)
    cc.reset_cache()


@pytest.fixture(scope="module", params=list(CASES))
def toy(request, no_persistent_cache):
    return request.param, compile_toy_step(request.param)


def test_every_scope_of_the_table_is_in_the_compiled_step(toy):
    name, compiled = toy
    names = op_names(compiled)
    blocks = {"llama": ["mlp"], "mixtral": ["moe"], "ouro": ["mlp"]}[name]
    for top in ["embed", "attention", "ce_head", *blocks]:
        assert has_scope(names, top, wrapped_by="jvp("), top
        assert has_scope(names, top, wrapped_by="transpose("), top
        for inner in DEVICE_SCOPES[top]:
            assert has_scope(names, inner, inside=top), (top, inner)
    # the looped stack alone opens ce_head/exit_gate: forward and backward
    # there, nowhere else
    for transform in ("jvp(", "transpose("):
        assert has_scope(names, "exit_gate", wrapped_by=transform,
                         inside="ce_head") == (name == "ouro"), (name, transform)
    # the backward kernels run only transposed, the forward one both ways
    # (recomputed under the layer's checkpoint)
    assert has_scope(names, "flash_fwd", wrapped_by="jvp(")
    for kernel in ("flash_dq", "flash_dkv"):
        assert has_scope(names, kernel, wrapped_by="transpose(")
    for inner in DEVICE_SCOPES["moe"] if name == "mixtral" else ():
        assert has_scope(names, inner, wrapped_by="transpose(", inside="moe")
    # outside the differentiated function: no transform wraps them
    assert has_scope(names, "grad_accum")
    assert has_scope(names, "clip", inside="optimizer")
    assert has_scope(names, "adamw", inside="optimizer")
    assert set(DEVICE_SCOPES) == {"embed", "attention", "mlp", "moe",
                                  "ce_head", "grad_accum", "optimizer"}


def test_scopes_are_metadata_the_program_is_the_same(toy, monkeypatch):
    name, scoped = toy
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    bare = compile_toy_step(name)
    assert not any(has_scope(op_names(bare), s) for s in ("attention", "optimizer"))
    assert opcode_census(bare) == opcode_census(scoped)
    assert sum(opcode_census(scoped).values()) > 100
    assert memory_totals(bare) == memory_totals(scoped)


def test_bucketed_regather_keeps_its_scope_inside_the_optimizer():
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        _world_of,
        lower_step_program,
        shrink_overrides,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    path = str(EX / "tiny_smoke_config.yaml")  # ships with bucketing on
    cfg = load_config(path, {"data.synthetic": True})
    cfg = load_config(path, {**shrink_overrides(cfg), "data.synthetic": True})
    asm = assemble_step_program(
        cfg, devices=jax.devices()[:_world_of(cfg, 8)], build_data=False)
    names = op_names(lower_step_program(asm)[1])
    assert has_scope(names, "zero1_bucket_ag", inside="optimizer")


# -- host side: spans on the profiler's clock -------------------------------


def host_event_names(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    data = ProfileData.from_file(str(files[-1]))
    return collections.Counter(
        ev.name for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)


def test_span_emits_an_annotation_of_its_name_under_an_open_trace(tmp_path):
    spans = SpanTimer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("data_wait", "host_sync", "log_metrics"):
            with spans.span(name):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    seen = host_event_names(tmp_path)
    assert all(seen[n] == 1 for n in ("data_wait", "host_sync", "log_metrics"))
    # the accounting is what it was
    assert set(spans.drain()) == {"data_wait", "host_sync", "log_metrics"}
    with SpanTimer(enabled=False).span("dispatch"):
        pass


def test_log_metrics_span_is_productive_and_lands_in_the_next_row(
        tmp_path, monkeypatch):
    from neuronx_distributed_training_tpu.telemetry.spans import (
        NON_PRODUCTIVE_SPANS,
    )

    made = collections.Counter()
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made[name] += 1
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    assert "log_metrics" not in NON_PRODUCTIVE_SPANS
    rows, summary = fit_tiny(tmp_path, max_steps=4)
    assert "time/log_metrics" not in rows[0]
    assert all(r["time/log_metrics"] > 0 for r in rows[1:])
    assert summary["goodput"]["goodput_fraction"] <= 1.0
    # with no capture open: the loop's few spans a step, nothing per layer or
    # per micro-batch
    assert {k: made[k] for k in ("data_wait", "dispatch", "host_sync",
                                 "log_metrics")} == {
        "data_wait": 4, "dispatch": 4, "host_sync": 4, "log_metrics": 4}
    # (where this is the worker's first fit(), the start-up timeline's phases
    # are annotated too, once each: they are no part of a step)
    once = {k: v for k, v in made.items() if k.startswith("startup/")}
    assert all(v == 1 for v in once.values()), once
    # + restart, compile, teardown
    assert sum(made.values()) - len(once) <= 4 * 4 + 3


# -- the compile counter ----------------------------------------------------


def fit_tiny(tmp_path, *, max_steps, at_step=None, overrides=None):
    """A tiny Llama ``fit()`` logging every step; ``at_step(step)`` runs
    inside the metric sink, ``overrides`` are dotted config keys.  Returns
    (metrics rows, run summary)."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config({
        "name": "scopes", "model_source": "hf", "seed": 7,
        "trainer": {"max_steps": max_steps, "log_every_n_steps": 1},
        "exp_manager": {"exp_dir": str(tmp_path),
                        "create_tensorboard_logger": False,
                        "log_files": False},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        "model": {"vocab_size": 128, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "max_position_embeddings": 32,
                  "optim": {"name": "adamw_fp32OptState", "lr": 1e-3}},
        "precision": {"type": "mixed_precision"},
    }, overrides)
    trainer = Trainer.from_config(cfg, enable_checkpointing=False)
    if at_step is not None:
        inner = trainer.exp.log_metrics

        def sink(step, metrics, **kw):
            inner(step, metrics, **kw)
            at_step(step)

        trainer.exp.log_metrics = sink
    trainer.fit()
    log_dir = Path(trainer.exp.log_dir)
    rows = [json.loads(l) for l in (log_dir / "metrics.jsonl").open()]
    return rows, json.loads((log_dir / "run_summary.json").read_text())


def test_a_forced_second_compile_shows_with_its_step(tmp_path):
    def force(step):
        if step == 3:  # a program XLA has not seen: a real backend compile
            jax.jit(lambda x: x * 3.25 + step).lower(
                jnp.ones((3, 5))).compile()

    _, summary = fit_tiny(tmp_path, max_steps=5, at_step=force)
    events = summary["compile_events"]
    # the two keys the benchmark's reader takes, and since the start-up
    # timeline the span open when the compile fired
    assert events and all(
        set(e) == {"step", "seconds", "phase"} for e in events)
    assert any(e["step"] == 0 for e in events)       # the step's own compile
    late = [e for e in events if e["step"] >= 1]
    # steady steps compile nothing; the forced program (and the constants it
    # is fed) is the only thing after the step's own compile
    assert late and {e["step"] for e in late} == {3}
    assert {e["phase"] for e in late} == {"log_metrics"}  # the sink's span
    assert [e["phase"] for e in events if e["step"] == 0] == ["compile"]
    assert all(e["seconds"] > 0 for e in late) and len(events) <= 50
    # fit() detaches the process's listener on its way out
    from neuronx_distributed_training_tpu.telemetry import recompile

    assert recompile._compile_sink is None


# -- how the trace partitioned the expert block -----------------------------


@pytest.mark.parametrize("family", ["dense", "mixtral_ep2", "mixtral_ep2_untraced"])
def test_moe_token_shards_is_a_static_fact_of_the_run(tmp_path, family, monkeypatch):
    """``moe_token_shards``: the shards the dropless expert block was traced
    under (here all 8 devices shard the batch: data 4 x expert 2), written
    with the compile census.  A dense model carries no such key; an MoE run
    whose trace recorded nothing carries null, never a key left out."""
    overrides = {} if family == "dense" else {
        "model.architecture": "mixtral",
        "model.moe": {"num_experts": 4, "top_k": 2, "dropless": True},
        "distributed_strategy.expert_model_parallel_size": 2,
    }
    if family == "mixtral_ep2_untraced":
        from neuronx_distributed_training_tpu.parallel import sharding as shd
        monkeypatch.setattr(shd, "trace_facts", lambda: None)
    _, summary = fit_tiny(tmp_path, max_steps=2, overrides=overrides)
    assert summary["n_chips"] == 8
    if family == "dense":
        assert "moe_token_shards" not in summary
    elif family == "mixtral_ep2_untraced":
        assert summary["moe_token_shards"] is None
    else:
        assert summary["moe_token_shards"] == 8


# -- how the flash kernels walk their blocks ---------------------------------


@pytest.mark.parametrize("window", [128, None], ids=["window_128", "no_window"])
def test_flash_band_is_a_static_fact_of_the_run(tmp_path, window):
    """``flash_band``: per distinct flash call of the step, the key blocks of
    the sequence against those a query block's walk covers (and the query
    blocks against a key block's): narrower than the sequence under a window
    shorter than it, the whole range without one; and which walk the call
    took: under a window no wider than its tiles the diagonal walk, with the
    sub-tiles it computes of those the band holds."""
    _, summary = fit_tiny(tmp_path, max_steps=1, overrides={
        "data.seq_length": 512, "data.global_batch_size": 8,
        "model.hidden_size": 256, "model.num_attention_heads": 2,
        "model.num_key_value_heads": 1, "model.max_position_embeddings": 512,
        "model.num_layers": 1, "model.sliding_window": window,
        "model.fusions": {"flash_attention": True, "flash_block_q": 128,
                          "flash_block_kv": 128},
    })
    (call,) = summary["flash_band"]
    assert call["seq"] == 512 and call["kv_blocks"] == call["q_blocks"] == 4
    if window is None:
        assert (call["kv_band"], call["q_band"]) == (4, 4)
        assert call["walk"] == "band" and "sub_tiles" not in call
    else:  # 128 rows and the 127 before them: two blocks either way
        assert (call["kv_band"], call["q_band"]) == (2, 2)
        assert call["kv_band"] < call["kv_blocks"]
        # tiles of 128 are one sub-tile: both of the band's are computed
        assert call["walk"] == "diagonal" and call["sub_tiles"] == [2, 2]


@pytest.mark.parametrize("granularity, expected", [
    ("full", {"granularity": "full", "kept": ["flash_o", "flash_lse"],
              "flash_fwd_per_layer_application": 1}),
    ("selective", {"granularity": "selective", "kept": "all",
                   "recomputed": ["attn_scores", "attn_probs"],
                   "flash_fwd_per_layer_application": 1}),
])
def test_remat_is_a_static_fact_of_the_run(tmp_path, granularity, expected):
    """``remat``: per scanned stack what a layer keeps for its backward and
    how often a layer application runs the flash forward kernel in a step
    (``models/llama.py::checkpoint_layer``), in ``run_summary.json``."""
    _, summary = fit_tiny(tmp_path, max_steps=1, overrides={
        "data.seq_length": 256, "data.global_batch_size": 8,
        "model.hidden_size": 256, "model.num_attention_heads": 2,
        "model.num_key_value_heads": 1, "model.max_position_embeddings": 256,
        "model.num_layers": 2,
        "model.activations_checkpoint_granularity": granularity,
        "model.fusions": {"flash_attention": True, "flash_block_q": 128,
                          "flash_block_kv": 128},
    })
    assert summary["remat"] == {"layers": expected}


def test_the_convolution_stacks_scopes_sit_inside_attention(no_persistent_cache):
    """``models/lfm2.py``: both operators run under ``attention``; the
    convolution half of a layer under ``short_conv`` with its middle under
    ``conv_gate``, an attention layer's head norms under ``qk_norm``, forward
    and backward; the flash kernels (heads of 64 dims) where they always
    are.  All three names are in ``FAMILY_SCOPES``."""
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.telemetry.spans import FAMILY_SCOPES
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    cfg = load_config(str(EX / "hf_lfm2_24b_a2b_config.yaml"), {
        **TOY, "data.synthetic": True, "model.num_attention_heads": 4,
        "model.num_key_value_heads": 1, "model.num_layers": 4,
        "model.layer_types": ["conv", "conv", "full_attention", "conv"],
        "model.vocab_size": 512, "model.num_experts": 4, "model.num_experts_per_tok": 2,
        "model.moe_intermediate_size": 128,
        "distributed_strategy.expert_model_parallel_size": 2,
        "exp_manager.checkpoint_callback_params": None})
    asm = assemble_step_program(cfg, devices=jax.devices()[:2], build_data=False)
    names = op_names(lower_step_program(asm)[1])
    assert {"short_conv", "conv_gate", "qk_norm"} <= set(FAMILY_SCOPES["attention"])
    for transform in ("jvp(", "transpose("):
        assert has_scope(names, "short_conv", wrapped_by=transform, inside="attention")
        assert has_scope(names, "conv_gate", wrapped_by=transform, inside="short_conv")
        assert has_scope(names, "qk_norm", wrapped_by=transform, inside="attention")
        assert has_scope(names, "mlp", wrapped_by=transform)
        assert has_scope(names, "moe", wrapped_by=transform)
    assert has_scope(names, "flash_fwd", inside="attention")
    assert has_scope(names, "flash_dkv", wrapped_by="transpose(", inside="attention")
    # the head norms belong to attention layers, the gate chain to convolution layers
    assert not has_scope(names, "qk_norm", inside="short_conv")
    assert not has_scope(names, "flash_fwd", inside="short_conv")


def test_the_mamba_layers_scopes_sit_inside_attention(no_persistent_cache):
    """``models/nemotron_h.py``: a Mamba-2 layer runs whole under
    ``attention/mamba`` with its convolution under ``mamba_conv``, the scan
    under ``ssd_scan`` and the gate and grouped norm under ``gated_norm``,
    forward and backward; an attention layer under ``attention`` with the
    flash kernels where they always are; a sparse layer under ``moe`` with the
    shared expert inside; no layer opens ``mlp``.  All four names are in
    ``FAMILY_SCOPES``."""
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.telemetry.spans import FAMILY_SCOPES
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    cfg = load_config(str(EX / "hf_nemotron3_nano_30b_a3b_config.yaml"), {
        **TOY, "data.synthetic": True, "model.num_attention_heads": 4,
        "model.num_key_value_heads": 1, "model.head_dim": 128, "model.num_layers": 3,
        "model.hybrid_override_pattern": "ME*", "model.mamba_num_heads": 4,
        "model.mamba_head_dim": 16, "model.ssm_state_size": 16, "model.n_groups": 2,
        "model.chunk_size": 32,
        "model.vocab_size": 512, "model.n_routed_experts": 4, "model.num_experts_per_tok": 2,
        "model.moe_intermediate_size": 128, "model.moe_shared_expert_intermediate_size": 128,
        "distributed_strategy.expert_model_parallel_size": 2,
        "exp_manager.checkpoint_callback_params": None})
    asm = assemble_step_program(cfg, devices=jax.devices()[:2], build_data=False)
    names = op_names(lower_step_program(asm)[1])
    assert {"mamba", "mamba_conv", "ssd_scan", "gated_norm"} <= set(FAMILY_SCOPES["attention"])
    for transform in ("jvp(", "transpose("):
        assert has_scope(names, "mamba", wrapped_by=transform, inside="attention")
        for inner in ("mamba_conv", "ssd_scan", "gated_norm"):
            assert has_scope(names, inner, wrapped_by=transform, inside="mamba")
        assert has_scope(names, "moe", wrapped_by=transform)
        assert has_scope(names, "shared", wrapped_by=transform, inside="moe")
        assert not has_scope(names, "mlp", wrapped_by=transform)
    assert has_scope(names, "flash_fwd", inside="attention")
    assert has_scope(names, "flash_dkv", wrapped_by="transpose(", inside="attention")
    assert not has_scope(names, "flash_fwd", inside="mamba")
