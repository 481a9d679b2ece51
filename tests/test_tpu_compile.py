"""Compiles for a described (not attached) TPU v5e, kept as tests.

The TPU's compiler is installed in the CPU test image and compiles for a
``v5e:2x2`` topology that is described, not attached.  It refuses what
interpret mode on the CPU mesh cannot see: a BlockSpec that does not tile, a
kernel that needs too much VMEM, a Mosaic call under GSPMD, a program that
does not fit 16 GB.  Nothing runs here, so these say nothing about results or
times; ``chip_smoke.py`` is the run.

The topology is described inside a module-scoped fixture (never at import):
only the xdist worker that is handed this file loads libtpu.  All such
compiles stay in this one file, in this process, with the persistent
compilation cache off around them (an entry written for a described chip
cannot be read back without one).
"""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_training_tpu.ops import flash_attention as fa

EX = os.path.join(os.path.dirname(os.path.dirname(__file__)), "examples", "conf")
HBM_BYTES = int(15.75 * 2**30)  # what the compiler gives a v5e program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no libtpu, or it is locked
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


# --------------------------------------------------------------------------
# the flash kernel: fwd, dq and dkv of every variant the main path uses
# --------------------------------------------------------------------------

#: name, batch, seq, q heads, kv heads, mask, segments, window, with_lse
FLASH_CASES = [
    ("causal_gqa_s8192", 1, 8192, 32, 8, False, False, None, False),
    ("attention_mask_b2", 2, 4096, 32, 8, True, False, None, False),
    ("segment_ids_b2", 2, 4096, 32, 8, False, True, None, False),
    ("sliding_window", 1, 8192, 32, 8, False, False, 4096, False),
    ("with_lse", 1, 8192, 32, 8, False, False, None, True),
    ("mha_s4096", 1, 4096, 32, 32, False, False, None, False),  # Llama-2-7B
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", FLASH_CASES, ids=[c[0] for c in FLASH_CASES])
def test_flash_fwd_bwd_compiles_for_v5e(topo, case, dtype):
    _, b, s, nh, nkv, masked, segmented, window, with_lse = case
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((b, s, nh, 128), dtype), sds((b, s, nkv, 128), dtype),
            sds((b, s, nkv, 128), dtype)]
    if masked or segmented:
        args.append(sds((b, s), jnp.int32))

    def loss(q, k, v, *rows):
        if with_lse:
            o, lse = fa.flash_attention_with_lse(
                q, k, v, causal=True, interpret=False)
            return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
        o = fa.flash_attention(
            q, k, v, causal=True, sliding_window=window, interpret=False,
            attention_mask=rows[0] if masked else None,
            segment_ids=rows[0] if segmented else None)
        return jnp.sum(o.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
    # fwd, dq, dkv — compiled Mosaic kernels, not the interpreter's jnp ops
    assert compiled.as_text().count("tpu_custom_call") == 3


# --------------------------------------------------------------------------
# whole train steps, assembled the way the trainer assembles them
# --------------------------------------------------------------------------


def _compile_step(topo, config, n_devices, overrides):
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    cfg = load_config(os.path.join(EX, config),
                      {"data.synthetic": True, **overrides})
    # code that asks jax.default_backend() still sees the CPU here; the
    # kernel's interpret default is steered to compiled by the test, not by
    # an option of the program
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        asm = assemble_step_program(
            cfg, devices=topo.devices[:n_devices], build_data=False)
        _, compiled = lower_step_program(asm)
    return compiled


STEP_CASES = {
    # chip_smoke.py's one-chip model: Llama-2-7B widths, 2 layers, seq 4096
    "one_chip_7b_widths": ("hf_llama_7B_config.yaml", 1, {
        "model.num_layers": 2,
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
        "data.global_batch_size": 1,
    }),
    # the product's path: tp2 x dp2 + SP + ZeRO-1, flash inside shard_map
    "tp2_dp2_sp_zero1": ("hf_llama_7B_config.yaml", 4, {
        "model.num_layers": 2,
        "distributed_strategy.tensor_model_parallel_size": 2,
        "data.global_batch_size": 4,
    }),
    # GQA with fewer kv heads per rank than q heads, seq 8192
    "llama3_8b_tp2_dp2": ("hf_llama3_8B_config.yaml", 4, {
        "model.num_layers": 3,
        "distributed_strategy.tensor_model_parallel_size": 2,
        "data.global_batch_size": 2,
    }),
    # flash inside the pipeline's pipe-manual region (a nested shard_map
    # over the remaining axes) and the pipe-ring broadcast of the 1f1b head
    "pp2_dp2": ("hf_llama_7B_config.yaml", 4, {
        "model.num_layers": 2,
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
        "distributed_strategy.pipeline_model_parallel_size": 2,
        "data.global_batch_size": 8,
    }),
    # the benchmark's looped-stack cell (benchmark/configs/ouro-2.6b.json):
    # published widths, 8 of 48 layers x 4 passes, seq 4096; every layer
    # application keeps only its input, the per-pass head is rematerialized
    "ouro_8_layers_4_passes": ("hf_ouro_2_6b_config.yaml", 1, {
        "model.num_layers": 8,
        "model.activations_checkpoint_granularity": "full",
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
        "data.global_batch_size": 1,
    }),
}


@pytest.mark.parametrize("name", list(STEP_CASES))
def test_train_step_compiles_for_v5e(topo, name):
    config, n_devices, overrides = STEP_CASES[name]
    compiled = _compile_step(topo, config, n_devices, overrides)
    assert "tpu_custom_call" in compiled.as_text(), (
        "the compiled step holds no Pallas kernel")
    ma = compiled.memory_analysis()
    # per device: arguments (donated, so outputs alias them) + temporaries.
    # The compiler itself refuses a program over the limit (a 4-layer
    # Llama-3-8B step is refused at 16.81 GiB); for the smaller programs the
    # reported sizes are additive and must fit too
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    if name != "llama3_8b_tp2_dp2":
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_BYTES


def test_two_micro_batches_do_not_fit_one_chip(topo):
    """The sizing fact behind chip_smoke.py's global_batch_size cut: at 7B
    widths x 2 layers a second micro-batch brings the fp32 accumulation
    carry, and the compiler refuses the program for one v5e chip."""
    config, n_devices, overrides = STEP_CASES["one_chip_7b_widths"]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        _compile_step(topo, config, n_devices,
                      {**overrides, "data.global_batch_size": 2})


def test_a_pass_that_keeps_its_layers_residuals_does_not_fit_one_chip(topo):
    """Why the looped-stack cell runs ``full``: under ``selective`` the pass
    is rematerialized whole and its 8 layers keep their residuals at once,
    which with 6.84 GiB of state the compiler refuses for one v5e (17.46 GiB);
    under ``full`` the same step takes 13.55 GiB."""
    config, n_devices, overrides = STEP_CASES["ouro_8_layers_4_passes"]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        _compile_step(topo, config, n_devices, {
            **overrides, "model.activations_checkpoint_granularity": "selective"})


# --------------------------------------------------------------------------
# named scopes (telemetry.spans.DEVICE_SCOPES) are metadata
# --------------------------------------------------------------------------


def test_named_scopes_leave_the_v5e_program_the_same(topo, monkeypatch):
    """One dense step (7B widths, one layer, two accumulated micro-batches)
    compiled with the step's ``jax.named_scope``s and without them: the same
    instructions by opcode and the same ``memory_analysis()``.  With them
    every scope is in the compiled text's ``op_name``s and the three flash
    kernels carry their own names."""
    import contextlib
    import re

    from test_scopes import memory_totals, opcode_census

    config, n_devices, overrides = STEP_CASES["one_chip_7b_widths"]
    overrides = {**overrides, "model.num_layers": 1,
                 "data.global_batch_size": 2}
    scoped = _compile_step(topo, config, n_devices, overrides)
    text = scoped.as_text()
    for scope in ("embed", "attention", "mlp", "ce_head"):
        assert f"jvp({scope})" in text or f"/{scope}/" in text, scope
        assert f"transpose(jvp({scope}))" in text or (
            f"/{scope}/" in text and "transpose(jvp())" in text), scope
    for scope in ("grad_accum", "optimizer/clip", "optimizer/adamw",
                  "attention/flash_fwd", "attention/flash_dq",
                  "attention/flash_dkv"):
        assert scope in text, scope
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        # pallas_call(name=...): the custom call's instruction is named so
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text), kernel

    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    bare = _compile_step(topo, config, n_devices, overrides)
    assert "optimizer/adamw" not in bare.as_text()
    assert opcode_census(bare) == opcode_census(scoped)
    assert sum(opcode_census(scoped).values()) > 300
    assert memory_totals(bare) == memory_totals(scoped)


# --------------------------------------------------------------------------
# the dropless expert block is partitioned by tokens (ops/moe.py)
# --------------------------------------------------------------------------


def test_dropless_block_is_partitioned_by_tokens_on_v5e(topo):
    """``jax.grad`` of the dropless block at Mixtral widths on ``(data 1,
    expert 4, model 1)``, 16 384 tokens sharded over the data axes: each chip
    sorts and multiplies its own 4 096 tokens x top-2 = 8 192 rows (not the
    global 32 768), nothing on the token path crosses chips, each expert
    weight is gathered over ``expert`` once and each expert-weight gradient
    is reduce-scattered once, in float32.  Master weights are float32 and
    cast per layer inside the differentiated function, as the step does."""
    import collections
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.ops import moe
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    cfg = moe.MoEConfig(num_experts=8, top_k=2, dropless=True)
    hidden, ffn, batch, seq = 4096, 14336, 4, 4096
    mesh = build_mesh(MeshConfig(expert_model_parallel_size=4),
                      devices=topo.devices[:4])

    def shaped(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map(
        shaped,
        jax.eval_shape(lambda k: moe.init_moe_params(k, hidden, ffn, cfg),
                       jax.random.PRNGKey(0)),
        moe.moe_param_specs(cfg))
    x = jax.ShapeDtypeStruct((batch, seq, hidden), jnp.bfloat16,
                             sharding=NamedSharding(mesh, shd.act_spec()))

    def loss(p, xx):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), p)
        y, aux = moe.moe_block(p, xx, cfg)
        return (y.astype(jnp.float32) ** 2).sum() + moe.weighted_router_loss(
            aux["router_logits"], aux["expert_idx"], cfg)

    with mesh, shd.use_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()

    rows = batch * seq * cfg.top_k // 4
    experts = {"gate_up": f"[8,{hidden},{2 * ffn}]", "down": f"[8,{ffn},{hidden}]"}
    ragged = re.findall(r"= (\w+\[[\d,]+\])\S* custom-call\(.*ragged", text)
    assert sorted(ragged) == sorted(
        [f"bf16[{rows},{2 * ffn}]", f"bf16[{rows},{ffn}]",
         f"bf16[{rows},{hidden}]", f"bf16[{rows},{hidden}]",
         "bf16" + experts["gate_up"], "bf16" + experts["down"]]), ragged

    # the compiler spreads one async collective over several fused
    # computations that share its channel_id: count channels per shape
    channels = collections.defaultdict(set)
    for shape, kind, channel in re.findall(
            r"= \(?(?:\w+\[[\d,]*\]\S* )*?(\w+\[[\d,]*\])\S*\)? "
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start)?\(.*channel_id=(\d+)", text):
        channels[(kind, shape)].add(channel)
    by_kind = collections.Counter(kind for kind, _ in channels)
    # no sorted rows (global or local), no global token list ([b*s, ...] or
    # [b, s, ...]: routing runs outside the region, on the global tokens,
    # and has to stay partitioned by them)
    token_dims = (batch * seq * cfg.top_k, rows, batch * seq)
    assert not any(shape.startswith(f"[{batch},{seq},", shape.index("["))
                   or any(f"[{n}," in shape for n in token_dims)
                   for _, shape in channels), channels
    assert by_kind["all-to-all"] == by_kind["collective-permute"] == 0
    for name, shape in experts.items():
        gathered = "bf16" + shape
        assert len(channels[("all-gather", gathered)]) == 1, (name, channels)
        scattered = f"f32[2,{shape[3:]}"
        assert len(channels[("reduce-scatter", scattered)]) == 1, (name, channels)
    assert by_kind["all-gather"] == by_kind["reduce-scatter"] == 2, channels
