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
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe

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
    # the 32k cell's own call: a band of 3 of 16 key blocks, 12 of 64 query
    ("window_4096_s32768", 1, 32768, 32, 8, False, False, 4096, False),
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


def _compile_step_anew(topo, config, n_devices, overrides):
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


@pytest.fixture(scope="module")
def compile_step(topo):
    """``compile_step(config, n_devices, overrides)``: the step the trainer
    would assemble, compiled for the described chips and kept for the file by
    what it was compiled from, so that no two cases compile one program.  A
    case that patches the program compiles it anew (``_compile_step_anew``)."""
    kept = {}

    def compile_step(config, n_devices, overrides):
        key = (config, n_devices, repr(sorted(overrides.items())))
        if key not in kept:
            kept[key] = _compile_step_anew(topo, config, n_devices, overrides)
        return kept[key]

    return compile_step


def _flash_forward_calls(compiled) -> int:
    """Distinct forward-kernel custom calls in the compiled text (one in a
    scan's body runs once a layer)."""
    return sum("tpu_custom_call" in line and "custom-call(" in line and "flash_fwd" in line
               for line in compiled.as_text().splitlines())


def _held_rows_operand(text: str) -> set:
    """The row counts of the bf16 results of the ragged dots in a compiled
    step's ``text``: where a range of the experts is held, the sorted-rows
    operands of the one-pass tiers and of the slices (``ops/moe.py::_HELD_ROWS``
    x the even share)."""
    return {int(rows) for rows in re.findall(
        r"= bf16\[(\d+),\d+\]\S* custom-call\(.*ragged", text)}


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
    # application keeps its input and the flash forward kernel's outputs, the
    # per-pass head is rematerialized
    # the benchmark's four-chip cell (benchmark/configs/mixtral-8x7b.json):
    # published widths, 1 of 32 layers, ep 4 x ZeRO-1, one sequence a chip.
    # The compiler takes it; its report reads 5.64 GiB of state and 8.54 GiB
    # of temporaries, which counts both ways through the experts, of which a
    # step runs one (13.17 GiB while both ways kept weights of all 8 experts'
    # shape between the passes, PRs 28-41; 6.66 when the weights always
    # travelled, PR 25)
    "mixtral_1_layer_ep4": ("hf_mixtral_8x7b_config.yaml", 4, {
        "model.num_layers": 1,
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
        "data.global_batch_size": 4,
    }),
    "ouro_8_layers_4_passes": ("hf_ouro_2_6b_config.yaml", 1, {
        "model.num_layers": 8,
        "model.activations_checkpoint_granularity": "full",
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
        "data.global_batch_size": 1,
    }),
}


@pytest.mark.parametrize("name", list(STEP_CASES))
def test_train_step_compiles_for_v5e(compile_step, name):
    compiled = compile_step(*STEP_CASES[name])
    assert "tpu_custom_call" in compiled.as_text(), (
        "the compiled step holds no Pallas kernel")
    ma = compiled.memory_analysis()
    # per device: arguments (donated, so outputs alias them) + temporaries.
    # The compiler itself refuses a program over the limit (a 4-layer
    # Llama-3-8B step is refused at 16.81 GiB); for the smaller programs the
    # reported sizes are additive and must fit too
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    if name not in ("llama3_8b_tp2_dp2", "mixtral_1_layer_ep4"):
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_BYTES
    if name == "ouro_8_layers_4_passes":
        # the layers' rerun does not call the forward kernel (two before the
        # kernel's outputs were kept; the report read 6.85 + 6.70 GiB then)
        assert _flash_forward_calls(compiled) == 1
        assert ma.temp_size_in_bytes < 7.6 * 2**30
    if name == "mixtral_1_layer_ep4":
        # between the passes the exchange keeps a chip's own 2 experts' cast
        # weights on both ways: no step pads them to the gathered 8's shape
        assert ma.temp_size_in_bytes < 9.0 * 2**30
        assert not re.search(r"bf16\[8,(4096,28672|14336,4096)\]\S* pad\(",
                             compiled.as_text())


def test_two_micro_batches_do_not_fit_one_chip(compile_step):
    """The sizing fact behind chip_smoke.py's global_batch_size cut: at 7B
    widths x 2 layers a second micro-batch brings the fp32 accumulation
    carry, and the compiler refuses the program for one v5e chip."""
    config, n_devices, overrides = STEP_CASES["one_chip_7b_widths"]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        compile_step(config, n_devices, {**overrides, "data.global_batch_size": 2})


def test_a_pass_that_keeps_its_layers_residuals_does_not_fit_one_chip(compile_step):
    """Why the looped-stack cell runs ``full``: under ``selective`` the pass
    is rematerialized whole and its 8 layers keep their residuals at once,
    which with 6.84 GiB of state the compiler refuses for one v5e (17.46 GiB);
    under ``full`` the same step takes 14.31 GiB (13.55 before every layer
    application kept its kernel's outputs)."""
    config, n_devices, overrides = STEP_CASES["ouro_8_layers_4_passes"]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        compile_step(config, n_devices, {
            **overrides, "model.activations_checkpoint_granularity": "selective"})


# --------------------------------------------------------------------------
# named scopes (telemetry.spans.DEVICE_SCOPES) are metadata
# --------------------------------------------------------------------------


def test_named_scopes_leave_the_v5e_program_the_same(topo, compile_step, monkeypatch):
    """One dense step (7B widths, one layer, two accumulated micro-batches)
    compiled with the step's ``jax.named_scope``s and without them: the same
    instructions by opcode and the same ``memory_analysis()``.  With them
    every scope is in the compiled text's ``op_name``s and the three flash
    kernels carry their own names."""
    import contextlib

    from test_scopes import memory_totals, opcode_census

    config, n_devices, overrides = STEP_CASES["one_chip_7b_widths"]
    overrides = {**overrides, "model.num_layers": 1,
                 "data.global_batch_size": 2}
    scoped = compile_step(config, n_devices, overrides)
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
    bare = _compile_step_anew(topo, config, n_devices, overrides)
    assert "optimizer/adamw" not in bare.as_text()
    assert opcode_census(bare) == opcode_census(scoped)
    assert sum(opcode_census(scoped).values()) > 300
    assert memory_totals(bare) == memory_totals(scoped)


# --------------------------------------------------------------------------
# the dropless expert block is partitioned by tokens (ops/moe.py)
# --------------------------------------------------------------------------


def test_dropless_block_is_partitioned_by_tokens_on_v5e(topo):
    """``jax.grad`` of the dropless block at Mixtral widths on ``(data 1,
    expert 4, model 1)``, 16 384 tokens sharded over the data axes, holds two
    ways through the experts and takes one by the routing it meets.  The rows
    travel: each chip keeps its 2 resident experts and multiplies the rows
    that chose them, up to twice its fair 8 192 (all-gather of the token
    shards, all-to-all of the outputs and of the rows' cotangents; the
    resident weights' gradients come out of the kernel where they live).  Or,
    when a chip would receive more, the weights travel: each chip multiplies
    its own 8 192 rows against all 8 experts, gathered over ``expert`` in
    bf16, and their gradients, float32 out of the kernel, are reduce-scattered
    back.  Master weights are float32 and cast per layer inside the
    differentiated function, as the step does."""
    import collections

    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    cfg = moe.MoEConfig(num_experts=8, top_k=2, dropless=True)
    hidden, ffn, batch, seq, ep = 4096, 14336, 4, 4096, 4
    mesh = build_mesh(MeshConfig(expert_model_parallel_size=ep),
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

    with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()

    tokens = batch * seq // ep  # a chip's own
    own, bound = tokens * cfg.top_k, 2 * tokens * cfg.top_k
    assert traced == {"moe_token_shards": ep, "moe_expert_exchange": "tokens",
                      "moe_row_bounds": [bound]}
    # per way: two ragged dots forward, their two transposes for the rows and
    # two for the weights; no forward runs twice
    resident = cfg.num_experts // ep
    ragged = collections.Counter(
        re.findall(r"= (\w+\[[\d,]+\])\S* custom-call\(.*ragged", text))
    assert ragged == collections.Counter({
        **{f"bf16[{rows},{width}]": times for rows in (own, bound)
           for width, times in ((2 * ffn, 1), (ffn, 1), (hidden, 2))},
        f"bf16[{resident},{hidden},{2 * ffn}]": 1, f"bf16[{resident},{ffn},{hidden}]": 1,
        f"f32[{cfg.num_experts},{hidden},{2 * ffn}]": 1,
        f"f32[{cfg.num_experts},{ffn},{hidden}]": 1}), ragged

    # the compiler spreads one async collective over several fused
    # computations that share its channel_id: count channels per shape
    channels = collections.defaultdict(set)
    for shape, kind, channel in re.findall(
            r"= \(?(?:\w+\[[\d,]*\]\S* )*?(\w+\[[\d,]*\])\S*\)? "
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start)?\(.*channel_id=(\d+)", text):
        channels[(kind, shape)].add(channel)
    by_kind = collections.Counter(kind for kind, _ in channels)
    assert by_kind["collective-permute"] == 0
    # the rows' way: the token shards gathered (rows, gate weights, choices),
    # outputs and the rows' and gate weights' cotangents returned.  The
    # weights' way: each expert weight gathered in bf16, once a pass, its
    # gradient reduce-scattered once in float32.  And no other gather: routing runs
    # outside the region on the global tokens and stays partitioned by them
    gathered = ep * tokens
    # (the compiler spells the cotangent's gather with a leading 1 or not)
    assert {shape.replace("[1,", "[") for kind, shape in channels
            if kind == "all-gather"} == {
        f"bf16[{gathered},{hidden}]", f"bf16[{gathered},{cfg.top_k}]",
        f"s32[{gathered * cfg.top_k}]",
        f"bf16[{cfg.num_experts},{hidden},{2 * ffn}]",
        f"bf16[{cfg.num_experts},{ffn},{hidden}]"}, channels
    assert {shape for kind, shape in channels if kind == "all-to-all"} == {
        f"bf16[{ep},{tokens},{hidden}]", f"f32[{ep},{tokens},{cfg.top_k}]"}, channels
    assert {shape for kind, shape in channels if kind == "reduce-scatter"} == {
        f"f32[{resident},{hidden},{2 * ffn}]", f"f32[{resident},{ffn},{hidden}]"}, channels


# --------------------------------------------------------------------------
# the mixed stack (models/laguna.py) at the benchmark's cut
# --------------------------------------------------------------------------

#: the cell ``laguna-s2.1-pretrain-ep32`` (benchmark/configs/laguna-s-2.1.json):
#: published widths, layer 0 and the period that follows it, experts 0-7 of 256
#: held, 1/8 of the vocabulary, one sequence of 8192
LAGUNA_CUT = {
    "model.num_hidden_layers": 5, "model.vocab_size": 12544,
    "model.num_experts_held": [0, 8],
    "distributed_strategy.expert_model_parallel_size": 1,
    "data.global_batch_size": 1,
}


@pytest.mark.parametrize("nh, window, block_kv, masked", [
    (72, 512, 512, False), (72, 512, 512, True), (48, None, None, False)],
    ids=["window-512-group-9", "window-512-group-9-attention-mask", "causal-group-6"])
def test_flash_compiles_at_the_mixed_stacks_shapes(topo, nh, window, block_kv, masked):
    """Both sides of the flash kernels' choice of walk at the window layers'
    shape: the diagonal walk's three kernels, and with an ``attention_mask``
    the band walk's at tiles of 512 x 512; then the full layers' causal call."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((1, 8192, n, 128), jnp.bfloat16, sharding=one_chip)
            for n in (nh, 8, 8)]
    if masked:
        args.append(jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip))

    def loss(q, k, v, mask=None):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, sliding_window=window, block_kv=block_kv,
            attention_mask=mask, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_the_mixed_stacks_cell_fits_one_v5e_under_full(compile_step):
    """9.06 GiB of state (811 M parameters): under ``full`` the compiler takes
    the step.  Its report of temporaries counts both ways through the held
    experts (the one pass over the narrow operand, slices of the wide one), of
    which a step runs one."""
    compiled = compile_step("hf_laguna_s_2_1_config.yaml", 1, LAGUNA_CUT)
    # the window layers' scan, and the two full layers' runs of one (each
    # merged with its rerun); a fourth, the window layers' rerun, before the
    # kernel's outputs were kept
    assert _flash_forward_calls(compiled) == 3
    # the even share is 8192 x 10 x 8 / 256 rows
    assert _held_rows_operand(compiled.as_text()) == {
        int(m * 2560) for m in moe._HELD_ROWS} == {3840, 5120}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert 8.9 * 2**30 < ma.argument_size_in_bytes < 9.2 * 2**30
    # 8 531 569 152, over the 8 269 037 056 at one operand of 3 x (7 680 rows):
    # the report does not follow the operand where the compiler, given the
    # room, rematerialises less by itself; the step fits, which the chip needs
    assert ma.temp_size_in_bytes < 8.1 * 2**30


@pytest.mark.slow   # one whole-step compile more of a step this file compiles: a minute
def test_the_mixed_stacks_cell_is_refused_under_selective(compile_step):
    """Why the cell runs ``full`` (PR 36): ``selective`` (five layers'
    residuals at 8192 tokens) is refused for one v5e at 19.67 GiB.  A
    ``selective`` step that began to fit would be news, not a fault."""
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        compile_step("hf_laguna_s_2_1_config.yaml", 1, {
            **LAGUNA_CUT, "model.activations_checkpoint_granularity": "selective"})


# --------------------------------------------------------------------------
# latent attention (models/kanana.py) at the benchmark's cut
# --------------------------------------------------------------------------

#: the cell ``kanana2-30b-pretrain-8k-ep8`` (benchmark/configs/kanana-2-30b-a3b.json):
#: published widths, layer 0 and five sparse layers, experts 0-15 of 128 held,
#: 1/8 of the vocabulary, two sequences of 8192 in one micro-batch
KANANA_CUT = {
    "model.num_hidden_layers": 6, "model.vocab_size": 16032,
    "model.num_experts_held": [0, 16],
    "distributed_strategy.expert_model_parallel_size": 1,
    "data.global_batch_size": 2,
}


@pytest.mark.parametrize("d_qk, block_kv", [(192, None), (256, None), (192, 2048)],
                         ids=["192-as-fed", "256-padded", "192-key-tile-2048"])
def test_flash_compiles_where_score_dims_are_not_value_dims(topo, d_qk, block_kv):
    """The band walk's three kernels at the cell's shape, q and k of 192 (fed
    whole: the block's last dim is the array's) or of 256, v of 128; at the
    default key tile of 2048 the dkv kernel would need 16.73 MiB of the 16 MiB
    of VMEM, so score dims past one lane width take 1024."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((2, 8192, 32, d), jnp.bfloat16, sharding=one_chip)
            for d in (d_qk, d_qk, 128)]

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_kv=block_kv,
                                          interpret=False).astype(jnp.float32))

    lower = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args)
    if block_kv == 2048:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|vmem"):
            lower.compile()
        return
    assert lower.compile().as_text().count("tpu_custom_call") == 3


def test_the_latent_attention_cell_fits_one_v5e_under_full(compile_step):
    """8.25 GB of state (687.5 M parameters) and two sequences of 8192: under
    ``full`` the compiler takes the step (``selective`` is refused at 22.05 GiB
    of 15.75: PERF.md section 4).  Its report of temporaries counts both ways
    through the held experts (the one pass over the narrow operand, slices of
    the wide one), of which a step runs one."""
    compiled = compile_step("hf_kanana_2_30b_a3b_config.yaml", 1, KANANA_CUT)
    # the dense layer's and the sparse scan's; their reruns made three before
    # the kernel's outputs were kept
    assert _flash_forward_calls(compiled) == 2
    # the even share is 16 384 x 6 x 16 / 128 rows
    assert _held_rows_operand(compiled.as_text()) == {
        int(m * 12288) for m in moe._HELD_ROWS} == {18432, 24576}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert 7.6 * 2**30 < ma.argument_size_in_bytes < 7.8 * 2**30
    # 10 746 421 760; at one operand of 3 x (36 864 rows) 11 081 480 192; and
    # under what the step reports with its dense layer merged with its rerun
    # (the next case), so the barrier's removal shows here
    assert ma.temp_size_in_bytes <= 11_081_480_192


@pytest.mark.slow   # the Kanana cell's whole step a second time, patched: 70 s
def test_the_latent_attention_cell_keeps_less_with_its_dense_layer_rematerialized(
        topo, monkeypatch):
    """Why ``models/kanana.py`` checkpoints a run of one layer with
    ``prevent_cse``: merged with its rerun, as every other stack's run of one
    is, the dense layer keeps its activations through the step beside the
    five sparse layers' kernel outputs: 11.13 GB of temporaries where the
    step with the barrier reports 10.75 (11.57 and 11.08 at one operand of 3 x
    the even share).  While the held experts' operand was 4 x the even share
    that decided the fit (the merged step was refused for one v5e, 15.89 GiB
    of 15.75); since 3 x it fits, and the barrier buys 0.36 GiB of room."""
    from neuronx_distributed_training_tpu.models import llama

    real = llama.checkpoint_layer
    monkeypatch.setattr(
        llama, "checkpoint_layer",
        lambda body, cfg, *, stack, prevent_cse=False: real(body, cfg, stack=stack))
    merged = _compile_step_anew(topo, "hf_kanana_2_30b_a3b_config.yaml", 1, KANANA_CUT)
    assert merged.memory_analysis().temp_size_in_bytes > 10_746_421_760 + 0.3 * 2**30


# --------------------------------------------------------------------------
# the gated short convolution / 64-dim-head attention stack (models/lfm2.py)
# at the benchmark's cut
# --------------------------------------------------------------------------

#: the cell ``lfm2-24b-pretrain-8k-ep8`` (benchmark/configs/lfm2-24b-a2b.json):
#: published widths, layers 0-7 (c c a c c c a c: both dense layers and six
#: sparse ones), experts 0-7 of 64 held, 1/8 of the vocabulary, two sequences
#: of 8192 in one micro-batch
LFM2_CUT = {
    "model.num_hidden_layers": 8, "model.vocab_size": 8192,
    "model.num_experts_held": [0, 8],
    "distributed_strategy.expert_model_parallel_size": 1,
    "data.global_batch_size": 2,
}


@pytest.mark.parametrize("block_q, block_kv, fits", [
    (None, None, True), (512, 1024, True), (512, 2048, True),
    (1024, 2048, False), (512, 4096, False)],
    ids=["default-1024x1024", "512x1024", "512x2048", "1024x2048-refused",
         "512x4096-refused"])
def test_flash_compiles_at_64_dim_heads(topo, block_q, block_kv, fits):
    """The band walk's three kernels at the cell's shape, 32 query / 8
    key-value heads of 64 dims fed as they are (every block's last dim is the
    array's 64); the default is the square tile of 1024, and the tiles past
    512 x 2048 or 1024 x 1024 need more than the 16 MiB of VMEM a kernel gets."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((2, 8192, nh, 64), jnp.bfloat16, sharding=one_chip)
            for nh in (32, 8, 8)]

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_q=block_q,
                                          block_kv=block_kv, interpret=False
                                          ).astype(jnp.float32))

    lower = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args)
    if not fits:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|vmem"):
            lower.compile()
        return
    text = lower.compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "bf16[2,32,8192,64]" in text and "bf16[2,32,8192,128]" not in text   # nothing padded


def test_the_short_convolution_cell_fits_one_v5e_at_depth_8(compile_step):
    """8.84 GB of state (736.9 M parameters) and two sequences of 8192: under
    ``full`` the compiler takes the step at depth 8 (its report of temporaries
    counts both ways through the held experts, of which a step runs one).
    Depth 9, one more sparse convolution layer (1.03 GiB of state and 0.34 of
    gradients), it refuses by 131 MiB, which decided the benchmark's depth
    (PERF.md section 4; not compiled here: a minute of this file's time).
    The two attention layers are runs of one, merged with their reruns: each
    calls the forward kernel once."""
    compiled = compile_step("hf_lfm2_24b_a2b_config.yaml", 1, LFM2_CUT)
    assert _flash_forward_calls(compiled) == 2
    text = compiled.as_text()
    assert "short_conv" in text and "conv_gate" in text and "qk_norm" in text
    assert "conv_gate_fwd" in text and "conv_gate_bwd" in text
    # the even share is 16 384 x 4 x 8 / 64 rows
    assert _held_rows_operand(text) == {int(m * 8192) for m in moe._HELD_ROWS} == {12288, 16384}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert 8.2 * 2**30 < ma.argument_size_in_bytes < 8.3 * 2**30
    # 8 990 563 840, over the 8 726 649 344 at one operand of 3 x (24 576
    # rows): with the room the compiler rematerialises 1 operation by itself
    # where it rematerialised 9, and keeps what it no longer recomputes
    assert ma.temp_size_in_bytes < 8.5 * 2**30
    assert len(re.findall(r"\.remat\d* = ", text)) <= 2


# --------------------------------------------------------------------------
# the single-mixer stack (models/nemotron_h.py) at the benchmark's cut
# --------------------------------------------------------------------------

#: the cell ``nemotron3-nano-pretrain-8k-ep16``
#: (benchmark/configs/nemotron-3-nano-30b-a3b.json): published widths, the
#: pattern's first nine layers (MEMEM*EME), experts 0-7 of 128 held, 1/8 of the
#: vocabulary, two sequences of 8192 in one micro-batch
NEMOTRON_CUT = {
    "model.num_hidden_layers": 9, "model.vocab_size": 16384,
    "model.num_experts_held": [0, 8],
    "distributed_strategy.expert_model_parallel_size": 1,
    "data.global_batch_size": 2,
}


def test_the_state_space_cell_fits_one_v5e_at_depth_9(compile_step):
    """8.0 GB of state (667 M parameters) and two sequences of 8192: under
    ``full`` the compiler takes the step at depth 9 with the scan walking
    blocks of 4 chunks that keep their inputs only (all 64 chunks at once it
    reported 0.69 GiB more and rematerialized 44 values by itself; depth 10,
    one more Mamba-2 layer, is accepted too: PERF.md section 4; not compiled
    here).  The one attention layer calls the forward kernel once, 16 query
    heads a key/value head at 128 dims with no rope; the held experts'
    operands are 1.5 x and 2 x the even share of 16 384 x 6 x 8 / 128 rows,
    1856 wide, unpadded, through the tiled grouped matmuls."""
    compiled = compile_step("hf_nemotron3_nano_30b_a3b_config.yaml", 1, NEMOTRON_CUT)
    assert _flash_forward_calls(compiled) == 1
    text = compiled.as_text()
    for scope in ("mamba", "mamba_conv", "ssd_scan", "gated_norm"):
        assert scope in text
    # widths of 2688 and 1856 are no whole multiples of 256: the held rows go
    # through megablox's tiled kernels, four gmm and two tgmm a pass there
    # and back, and no ragged dot is left (ops/moe.py::_tiles)
    assert _held_rows_operand(text) == set() and "ragged" not in text
    tiled = re.findall(r"%(t?gmm)\.\d+ = (\S+?)\{\S* custom-call\(", text)
    assert [int(m * 6144) for m in moe._HELD_ROWS] == [9216, 12288]      # whole tiles of 512
    assert {shape for _, shape in tiled} == {
        "bf16[9216,1856]", "bf16[9216,2688]", "bf16[12288,1856]", "bf16[12288,2688]",
        "f32[8,2688,1856]", "f32[8,1856,2688]"}
    for rows in (9216, 12288):                                            # nothing padded
        assert f"bf16[{rows},1920]" not in text and f"bf16[{rows},2816]" not in text
    assert "bf16[2,32,8192,128]" in text and "bf16[2,2,8192,128]" in text    # 32 / 2 heads as fed
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert 7.4 * 2**30 < ma.argument_size_in_bytes < 7.5 * 2**30
    # 8 439 808 512; at one operand of 3 x (18 432 rows) 8 752 347 648
    assert ma.temp_size_in_bytes <= 8_752_347_648


# --------------------------------------------------------------------------
# attention whose keys a learned indexer chooses (models/keye.py) at the
# benchmark's cut
# --------------------------------------------------------------------------

#: the cell ``keye-vl2-30b-pretrain-8k-ep8``
#: (benchmark/configs/keye-vl-2.0-30b-a3b.json): published widths, six layers,
#: experts 0-15 of 128 held, 1/8 of the vocabulary, two sequences of 8192 in
#: one micro-batch
KEYE_CUT = {
    "model.num_hidden_layers": 6, "model.vocab_size": 18992,
    "model.num_experts_held": [0, 16],
    "distributed_strategy.expert_model_parallel_size": 1,
    "data.global_batch_size": 2,
}


def test_the_learned_selection_cell_fits_one_v5e_at_depth_6(compile_step):
    """7.9 GB of state (659 M parameters) and two sequences of 8192: under
    ``full`` the compiler takes the step at depth 6 (depth 7, 1.08 GiB of state
    and 0.36 of gradients more, is refused at 16.80 of 15.75 GiB, 16.73 before
    the loss's gradient was kept: PERF.md section 4; not compiled here).  The
    family's own masked flash kernels (32 MiB of VMEM each: at tiles of 512 x 2048 the dkv kernel with a block of
    the int8 mask beside its operands needs 18.33 MiB) are called once a layer
    each, the forward's outputs kept across the rematerialized layer; the
    selection kernel (Mosaic accepts its 64 rows x up to 8192 float32 scores
    in VMEM) by the 12 chunks whose keys pass ``topk``, in the layer's forward
    and in its rerun: the rerun still runs the layer's first half up to the
    mask (the norm, q, k, v, the indexer's operands, the index scores, the
    selection) and the experts, and nothing of the indexer's loss, whose
    gradient the forward took and ``full`` keeps (72 MB a layer): the kernel
    that sums the heads' probabilities is called by the 16 chunks once.  The
    held experts' rows, 768 and 2048 wide, stay with XLA's ragged dot at 1.5 x
    and 2 x the even share of 16 384 x 8 x 16 / 128 rows."""
    compiled = compile_step("hf_keye_vl2_30b_a3b_config.yaml", 1, KEYE_CUT)
    assert _flash_forward_calls(compiled) == 1
    text = compiled.as_text()

    def calls(kernel):
        return [line for line in text.splitlines() if "tpu_custom_call" in line
                and "custom-call(" in line and f"/{kernel}/" in line]

    assert [len(calls(f"flash_sel_{kind}")) for kind in ("fwd", "dq", "dkv")] == [1, 1, 1]
    assert "s8[2,8192,8192]" in calls("flash_sel_dkv")[0]          # the mask as an operand
    selects = calls("dsa_select")
    assert len(selects) == 2 * 12 and len(calls("dsa_probs")) == 16
    # a chunk's scores in, its mask out: from 2560 keys (the first to select) to 8192
    assert any("s32[2,512,2560]" in line for line in selects)
    assert any("s32[2,512,8192]" in line for line in selects)
    for scope in ("indexer", "select", "indexer_loss", "qk_norm"):
        assert scope in text
    assert _held_rows_operand(text) == {int(m * 16384) for m in moe._HELD_ROWS} == {24576, 32768}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert 7.3 * 2**30 < ma.argument_size_in_bytes < 7.4 * 2**30
    # 10.75 (10.11 at one operand of 3 x the even share, 11.45 while the
    # backward held all sixteen chunks' ``p`` and score cotangents): the report
    # does not follow the operand where the compiler, given the room,
    # rematerialises less by itself (4 operations where it did 8); the step
    # fits, which is what the chip needs
    assert ma.temp_size_in_bytes < 11.0 * 2**30
