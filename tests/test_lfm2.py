"""A gated-short-convolution / attention stack with sigmoid-routed experts
(``models/lfm2.py``): layers whose operator (a depthwise causal convolution of
three taps between two input-dependent gates, or grouped-query attention with
an RMS norm on every head's q and k) and feed-forward (dense, or experts
chosen by ``sigmoid score + bias`` whose bias moves by the load after every
optimizer step, of which this program may hold a range) vary independently,
under a head tied to the embedding.  Held against the benchmark's plain
reference (``benchmark/references/lfm2.py``, float32, nothing of the program)
by the rungs of ``tests/family_ladder.py``, each of seven omissions and an
omitted bias update shown to fail the parity the first holds and the shares of
all held ranges shown to add up to the whole; the convolution's middle held
against an explicit loop over positions, with left padding and packed
documents too; the flash kernels at 64-dim heads held against core attention;
the accepted families' programs shown untouched."""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_ladder
from benchmark.reference import leaf_names
from family_ladder import FP32
from neuronx_distributed_training_tpu.models import lfm2
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.models.laguna import stack_plan
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import short_conv as conv_ops

#: the published shape at toy widths: every kind of layer (c c a c: two dense
#: layers, an attention and a convolution layer with experts), 4 query / 2
#: key-value heads of 16 dims, 3
#: taps, 16 experts of which a token takes 4 and 4 are held, a tied head
MODEL = dict(
    architecture="lfm2", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    layer_types=["conv", "conv", "full_attention", "conv"], conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    initializer_range=0.02, num_dense_layers=2, num_experts=16, num_experts_held=[0, 4],
    num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True, router_bias_update_rate=0.001,
    activations_checkpoint_granularity="full")
SEQ = 32
STACKS = {"conv_dense", "full_sparse", "conv_sparse"}
CONV, FULL = "layers/conv_sparse/", "layers/full_sparse/"
_H, _HEADS, _GROUPS, _D = 64, 4, 2, 16
_CONV = 2 * _H * 4 * _H + 2 * 3 * _H + 2 * _H
_ATTENTION = (2 * _H * (_HEADS + 2 * _GROUPS) * _D + 2 * _HEADS * _D * _H
              + 4 * _HEADS * _D * 4097 / 2)

TOY = family_ladder.Toy(
    module=lfm2, config_class=lfm2.Lfm2Config, reference="lfm2", model=MODEL, seq=SEQ,
    bias=tuple(("layers", stack, "mlp", "router", "bias") for stack in ("full_sparse", "conv_sparse")),
    omissions=("in_gate", "out_gate", "taps", "qk_norm", "rope", "bias", "renorm"),
    shapes={
        CONV + "conv/in_proj/w": (1, 64, 192), CONV + "conv/out_proj/w": (1, 64, 64),
        CONV + "conv/taps/w": (1, 3, 64),
        FULL + "attn/qkv/w": (1, 64, (4 + 2 * 2) * 16), FULL + "attn/o/w": (1, 64, 64),
        FULL + "attn/q_norm/scale": (1, 16), FULL + "attn/k_norm/scale": (1, 16),
        "layers/conv_dense/mlp/gate_up/w": (2, 64, 256),
        CONV + "mlp/experts/down": (1, 4, 32, 64),                     # 4 of 16 held
        CONV + "mlp/router/w": (1, 64, 16), CONV + "mlp/router/bias": (1, 16)},
    refusals={
        "pipeline": ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
        "tensor": ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
        "context": ({}, {"context_parallel_size": 2}, "context_parallel_size.*halo"),
        "sequence-parallel": ({}, {"sequence_parallel": True}, "sequence_parallel.*halo"),
        "held-under-ep": ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
        "held-range": ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
        "conv-bias": ({"conv_bias": True}, {}, "conv_bias"),
        "no-expert-bias": ({"use_expert_bias": False}, {}, "use_expert_bias"),
        "unknown-operator": ({"layer_types": ["conv", "mamba"] * 4}, {}, "layer_types"),
        "yarn": ({"rope_parameters": {"rope_type": "yarn", "factor": 4}}, {}, "rope_type"),
        "bias-never-moves": ({"router_bias_update_rate": 0.0}, {}, "router_bias_update_rate"),
        "bias-rate-missing": ({"router_bias_update_rate": None}, {}, "router_bias_update_rate")},
    # the operators, and 4 slots a token x 4 of 16 held = 1 expected slot in
    # each of 2 sparse layers
    flops=(({}, {"attention": 3 * _CONV + _ATTENTION,
                 "mlp": 2 * 6 * _H * 128 + 2 * 6 * _H * 32 * 1.0,
                 "router": 2 * 2 * _H * 16, "head": 2 * _H * 256}),
           ({"num_experts": 0, "num_experts_held": None}, {"router": 0})),
    shares=(("conv_sparse", 8),),
    summary={"model_family": "Lfm2Config",
             "layer_kinds": {"conv_dense": 2, "full_sparse": 1, "conv_sparse": 1},
             "operator_kinds": {"conv": 3, "full_attention": 1},
             "short_conv": {"taps": 3, "way": "pallas", "bytes_per_token": 512},
             "moe_experts_held": [0, 4, 16], "moe_score_func": "sigmoid",
             # _HELD_ROWS x the even share, 2 x 32 x 4 x 4 / 16 = 64 rows
             "moe_row_bounds": [int(m * 64) for m in moe_ops._HELD_ROWS]},
    example=("hf_lfm2_24b_a2b_config.yaml", (), {"data.micro_batch_size": 1},
             {"operator_kinds": {"conv": 3, "full_attention": 1}}))


@pytest.fixture(scope="module")
def programs():
    return family_ladder.Programs(TOY)


class TestLadder(family_ladder.BiasLadder):
    toy = TOY

    def test_the_head_is_tied_and_every_stack_rematerialized_whole(self, programs, trained):
        names = leaf_names(programs.weights(11, spread=False))
        assert not any(n.startswith(("lm_head", "final_norm")) for n in names)   # embedding_norm
        remat = trained["summary"]["remat"]
        assert set(remat) == STACKS
        assert all(entry["granularity"] == "full" for entry in remat.values())


config = TOY.config


def test_the_published_stack_is_a_run_of_two_nine_periods_and_two_runs():
    types = (["conv", "conv", "full_attention", "conv"] * 10)
    cfg = config(num_hidden_layers=40, layer_types=types)
    cd, fs, cs = ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse")
    assert stack_plan(cfg.kinds) == [("run", cd, 2), ("periods", 9, ((fs, 1), (cs, 3))),
                                     ("run", fs, 1), ("run", cs, 1)]
    # the benchmark's depth: five runs, no period
    assert stack_plan(config(num_hidden_layers=8, layer_types=types[:8]).kinds) == [
        ("run", cd, 2), ("run", fs, 1), ("run", cs, 3), ("run", fs, 1), ("run", cs, 1)]


def test_loss_and_every_gradient_match_at_a_depth_with_periods(programs):
    """Depth 10, where the stack plan holds a periodic segment of two periods:
    the loads come back in the order of each kind's stack."""
    deep = {**MODEL, "num_hidden_layers": 10, "layer_types": (MODEL["layer_types"] * 3)[:10]}
    assert any(seg[0] == "periods" for seg in stack_plan(lfm2.Lfm2Config.from_config(deep, {}).kinds))
    found = programs.against(weights=3, tokens=1, model=deep)
    family_ladder.gradients_match(found, TOY.leaf_tol)
    family_ladder.bias_steers_unweighed(found, TOY)


# -- the convolution's middle ---------------------------------------------------


def loop_conv(bcz, taps, mask=None, segments=None):
    """The operator's middle by an explicit loop over positions (numpy,
    float64): ``y_t = C_t * sum_j w[j] * g_{t-2+j}``, ``g = B * z`` zeroed at
    padded positions and not carried across a document's start."""
    bcz, taps = np.asarray(bcz, np.float64), np.asarray(taps, np.float64)
    b, s, c3 = bcz.shape
    c, k = c3 // 3, taps.shape[0]
    gate_in, gate_out, z = bcz[..., :c], bcz[..., c:2 * c], bcz[..., 2 * c:]
    y = np.zeros((b, s, c))
    for i in range(b):
        for t in range(s):
            acc = np.zeros(c)
            for j in range(k):
                u = t - (k - 1) + j
                if u < 0 or (mask is not None and not mask[i, u]):
                    continue
                if segments is not None and segments[i, u] != segments[i, t]:
                    continue
                acc += taps[j] * gate_in[i, u] * z[i, u]
            y[i, t] = gate_out[i, t] * acc
    return y


@pytest.mark.parametrize("s", [24, 520], ids=["one-tile", "three-tiles"])
@pytest.mark.parametrize("rows", [None, "attention_mask", "segment_ids"])
def test_the_convolutions_middle_is_the_loop_over_positions(rows, s):
    """Forward and the gradients to B, C, z and the taps, against the loop's
    (by finite differences of the loop itself where autodiff has none: the
    loop is linear in each of its four inputs, so a directional derivative is
    the loop applied to the direction).  At 520 tokens the kernels walk three
    tiles of 256 rows (the last padded), so the halo of 8 rows before and after
    a tile carries the taps across both seams, forward and backward."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, c = 2, 8
    assert conv_ops._tile_rows(s, c) == min(s, 256)
    bcz = jax.random.normal(ks[0], (b, s, 3 * c), jnp.float32)
    taps = jax.random.normal(ks[1], (3, c), jnp.float32)
    ct = np.asarray(jax.random.normal(ks[2], (b, s, c), jnp.float32), np.float64)
    mask = np.arange(s)[None, :] >= np.array([[0], [7]])          # left padding
    # documents that end at, one before and one after a tile's seam among them
    segments = np.stack([np.arange(s) // 9, np.searchsorted(
        np.array([5, 255, 256, 257, 300, 511, 513]), np.arange(s), side="right")])
    kw, lkw = {}, {}
    if rows == "attention_mask":
        kw, lkw = {"attention_mask": jnp.asarray(mask)}, {"mask": mask}
    if rows == "segment_ids":
        kw, lkw = {"segment_ids": jnp.asarray(segments)}, {"segments": segments}
    y = conv_ops.gated_short_conv(bcz, taps, **kw)
    np.testing.assert_allclose(np.asarray(y), loop_conv(bcz, taps, **lkw), rtol=1e-5, atol=1e-5)
    d_bcz, d_taps = jax.grad(
        lambda x, w: jnp.sum(conv_ops.gated_short_conv(x, w, **kw) * ct), (0, 1))(bcz, taps)
    # the loop is bilinear in (B, z) and linear in C and in the taps: its
    # gradient to one element is the loop's value with that element's unit
    # direction in its place; checked on a sample of directions of each part
    rng = np.random.default_rng(0)
    x0, w0 = np.asarray(bcz, np.float64), np.asarray(taps, np.float64)
    seams = [t for t in (0, 1, 2, 254, 255, 256, 257, 258, 510, 511, 512, 513, s - 1) if t < s]
    for part in range(3):                                        # B, C, z
        for n in range(6 + len(seams)):
            i, ch = rng.integers(b), rng.integers(c)
            t = seams[n - 6] if n >= 6 else rng.integers(s)
            eps = np.zeros_like(x0)
            eps[i, t, part * c + ch] = 1.0
            want = np.sum((loop_conv(x0 + eps, w0, **lkw) - loop_conv(x0 - eps, w0, **lkw)) * ct) / 2
            assert float(d_bcz[i, t, part * c + ch]) == pytest.approx(want, rel=1e-4, abs=1e-4)
    for j in range(3):
        for ch in range(c):
            eps = np.zeros_like(w0)
            eps[j, ch] = 1.0
            want = np.sum((loop_conv(x0, w0 + eps, **lkw) - loop_conv(x0, w0 - eps, **lkw)) * ct) / 2
            assert float(d_taps[j, ch]) == pytest.approx(want, rel=1e-4, abs=1e-4)
    if rows == "attention_mask":   # a padded position reaches nothing after it
        moved = bcz.at[1, :7].add(3.0)
        np.testing.assert_array_equal(
            np.asarray(conv_ops.gated_short_conv(moved, taps, **kw))[1, 7:], np.asarray(y)[1, 7:])


def test_what_the_kernels_are_told_and_what_they_refuse():
    """``keep[t, d]``: whether ``g_{t-d}`` counts for token ``t``; the taps and
    the halo are one block of 8 rows each."""
    mask = jnp.array([[0, 0, 1, 1, 1, 1]])
    segments = jnp.array([[0, 0, 0, 1, 1, 2]])
    keep = np.asarray(conv_ops._keep(1, 6, 3, mask, segments))[0]
    assert keep.shape == (6, 8) and not keep[:, 3:].any()
    np.testing.assert_array_equal(keep[:, 0], [0, 0, 1, 1, 1, 1])      # a real token itself
    np.testing.assert_array_equal(keep[:, 1], [0, 0, 0, 0, 1, 0])      # t-1 real, same document
    np.testing.assert_array_equal(keep[:, 2], [0, 0, 0, 0, 0, 0])
    plain = np.asarray(conv_ops._keep(1, 6, 3, None, None))[0]
    np.testing.assert_array_equal(plain[:, :3], [[1, 0, 0], [1, 1, 0]] + [[1, 1, 1]] * 4)
    assert conv_ops._tile_rows(8192, 2048) == 256 and conv_ops._tile_rows(8192, 4096) == 128
    assert conv_ops._tile_rows(20, 64) == 24 and conv_ops.bytes_per_token(2048) == 16384
    x = jnp.zeros((1, 16, 3 * 64))
    with pytest.raises(ValueError, match="9 taps"):
        conv_ops.gated_short_conv(x, jnp.zeros((9, 64)))
    with pytest.raises(ValueError, match="3 x 32"):
        conv_ops.gated_short_conv(x, jnp.zeros((3, 32)))
    with pytest.raises(ValueError, match="lane boundaries"):
        conv_ops.gated_short_conv(x, jnp.zeros((3, 64)), interpret=False)
    y = conv_ops.gated_short_conv(jnp.ones((1, 16, 3 * 64)), jnp.ones((1, 64)))   # one tap
    np.testing.assert_array_equal(np.asarray(y), 1.0)


def test_a_changed_token_moves_nothing_before_it_and_two_convolution_outputs_after(programs):
    """Causality and the halo: through ONE convolution layer's operator a
    token's change reaches its own position and the 2 after it, nothing else;
    through the whole stack (attention among its layers) nothing before it."""
    cfg, params = config(), programs.weights(1)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["conv_sparse"]["conv"])
    t = 11
    toks = programs.tokens(8, rows=1)

    @jax.jit
    def gaps(key):
        x = jax.random.normal(key, (1, SEQ, 64), jnp.float32)
        moved = x.at[0, t].add(1.0)
        other = toks.at[0, t].set((toks[0, t] + 1) % 256)
        logits = lambda ids: lfm2.forward(params, {"input_ids": ids}, cfg, FP32)[0]  # noqa: E731
        return (jnp.abs(lfm2._conv_block(lp, moved, cfg) - lfm2._conv_block(lp, x, cfg))[0],
                jnp.abs(logits(toks) - logits(other))[0].max(axis=-1))

    with jax.default_matmul_precision("highest"):
        d, gap = map(np.asarray, gaps(family_ladder.key_of(2)))
    assert list(np.flatnonzero(d.max(axis=-1) > 1e-6)) == [t, t + 1, t + 2]
    assert not np.any(gap[:t]) and gap[t] > 0


def test_left_padding_and_packed_documents_reach_both_operators(programs):
    """A left-padded row's real tokens see what the unpadded row's see (the
    convolution's zeros before the first real token, attention's masked keys,
    positions from the first real token); a packed row's second document
    reads as it does alone."""
    cfg, params = config(activations_checkpoint_granularity=None), programs.weights(1)
    toks, first = programs.tokens(6, rows=1, seq=20), programs.tokens(7, rows=1, seq=12)
    pad = 12

    @jax.jit
    def logits():
        padded = jnp.concatenate([jnp.zeros((1, pad), toks.dtype), toks], axis=1)
        mask = (jnp.arange(pad + 20) >= pad)[None].astype(jnp.int32)
        segments = jnp.concatenate([jnp.zeros((1, 12), jnp.int32), jnp.ones((1, 20), jnp.int32)], axis=1)
        run = lambda **batch: lfm2.forward(params, batch, cfg, FP32)[0]  # noqa: E731
        return (run(input_ids=toks), run(input_ids=padded, attention_mask=mask)[:, pad:],
                run(input_ids=jnp.concatenate([first, toks], axis=1), segment_ids=segments)[:, 12:])

    with jax.default_matmul_precision("highest"):
        alone, behind, packed = map(np.asarray, logits())
    np.testing.assert_allclose(behind, alone, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(packed, alone, rtol=2e-4, atol=2e-4)


# -- the head norms ---------------------------------------------------------------


def test_every_head_is_normed_before_the_rope_by_one_scale_a_side(programs):
    """``llama._attention_block`` with ``q_norm`` / ``k_norm`` leaves against
    the same block without them fed a qkv whose heads were normed by hand."""
    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.ops import rope as rope_ops

    cfg = config(activations_checkpoint_granularity=None)
    lc = cfg.llama
    lp = jax.tree_util.tree_map(lambda a: a[0], programs.weights(4)["layers"]["full_sparse"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), jnp.float32)
    cos, sin = rope_ops.rope_cos_sin(jnp.arange(SEQ)[None], rope_ops.rope_frequencies(16, theta=1e6))
    with jax.default_matmul_precision("highest"):
        got = llama._attention_block(lp, x, cos, sin, lc, FP32, sliding_window=None)
        qkv = x @ lp["qkv"]["w"]
        q, k, v = jnp.split(qkv, [64, 96], axis=-1)

        def rms(heads, scale):
            h = heads.reshape(2, SEQ, -1, 16)
            return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5) * scale

        q, k = rms(q, lp["q_norm"]["scale"]), rms(k, lp["k_norm"]["scale"])
        q, k = rope_ops.apply_rope(q, cos, sin), rope_ops.apply_rope(k, cos, sin)
        out = attn_ops.core_attention(q, k, v.reshape(2, SEQ, 2, 16), causal=True)
        want = out.reshape(2, SEQ, 64) @ lp["o"]["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    plain = {k: v for k, v in lp.items() if k not in ("q_norm", "k_norm")}
    with jax.default_matmul_precision("highest"):
        assert float(jnp.max(jnp.abs(
            llama._attention_block(plain, x, cos, sin, lc, FP32, sliding_window=None) - got))) > 1e-3


def test_the_route_renormalises_over_the_familys_epsilon():
    """The epsilon is a field the family sets: 1e-6 here, 1e-20 where nothing
    sets it (the route Kanana runs), and it shows where the chosen scores are
    small."""
    assert config().moe.renorm_eps == 1e-6 and moe_ops.MoEConfig().renorm_eps == 1e-20
    base = moe_ops.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid")
    x = jnp.ones((4, 16))
    w = jnp.full((16, 8), -1.0)                        # scores of sigmoid(-16): 1.1e-7
    router = {"w": w, "bias": jnp.zeros((8,))}
    tiny, _, _ = moe_ops.route(router, x, base)
    ours, _, _ = moe_ops.route(router, x, dataclasses.replace(base, renorm_eps=1e-6))
    s = float(jax.nn.sigmoid(-16.0))
    np.testing.assert_allclose(np.asarray(tiny), 0.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ours), s / (2 * s + 1e-6), rtol=1e-4)


# -- the kernels at heads of half a lane width ------------------------------------


@pytest.mark.parametrize("rows", [None, "attention_mask", "segment_ids"])
def test_flash_at_64_dim_heads_matches_core(rows):
    """The band walk's three kernels against core attention, 4 query heads a
    key/value head."""
    assert fa.flash_tileable(256, 256, 64, 8, 2, block_q=128, block_kv=128)
    family_ladder.flash_matches_core(64, 2, 8, 2, 64, 64, rows=rows)


# -- the accepted families' programs are untouched --------------------------------


@pytest.mark.parametrize("arch, extra", [
    ("llama", {}), ("mixtral", {"moe": {"num_experts": 4, "top_k": 2, "dropless": True}}),
    ("laguna", {"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
                "mlp_layer_types": ["dense", "sparse"]}),
    ("kanana", {"n_routed_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
                "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
                "kv_lora_rank": 16, "router_bias_update_rate": 0.001})])
def test_an_accepted_familys_program_knows_nothing_of_the_head_norms(arch, extra):
    """The per-head norm hangs on leaves only this family has: an accepted
    family's loss lowers with no ``qk_norm`` scope and no head-norm leaf, and
    to the same text when the block is handed a tree that names them absent
    (the block asks ``"q_norm" in lp`` and nothing else)."""
    family, cfg = resolve({"model": {
        "architecture": arch, "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "activations_checkpoint_granularity": None, **extra}})
    # shapes are all a lowering needs: nothing is drawn
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg, FP32))
    names = leaf_names(params)
    assert not any("q_norm" in n or "k_norm" in n or "conv" in n for n in names)
    rows = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"input_ids": rows, "labels": rows}
    text = jax.jit(lambda p, b: family.loss(cfg, FP32)(p, b, None)[0]).lower(
        params, batch).as_text(debug_info=True)
    assert "qk_norm" not in text and "short_conv" not in text and "conv_gate" not in text
    if arch == "kanana":   # the route's epsilon is the default's: the constant Kanana ran
        assert cfg.moe.renorm_eps == 1e-20


def test_kananas_route_lowers_to_the_text_it_lowered_to():
    """The epsilon became a field; at its default the sigmoid route's jaxpr
    holds the constant it held (1e-20), and the field's other value another."""
    cfg = moe_ops.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid")
    router = {"w": jnp.zeros((16, 8)), "bias": jnp.zeros((8,))}
    def jaxpr(route_cfg):
        return str(jax.make_jaxpr(lambda x: moe_ops.route(router, x, route_cfg))(
            jnp.zeros((4, 16))))

    text = jaxpr(cfg)
    assert "9.999999682655225e-21" in text                  # float32's 1e-20
    assert text == jaxpr(dataclasses.replace(cfg, renorm_eps=1e-20))
    other = jaxpr(dataclasses.replace(cfg, renorm_eps=1e-6))
    assert text != other and "logistic" in other


def test_the_family_answers_to_both_names_and_says_what_it_cannot():
    for arch in ("lfm2", "lfm2_moe"):
        family, cfg = resolve({"model": {**MODEL, "architecture": arch}})
        assert family is lfm2.FAMILY and cfg.family is family
    with pytest.raises(NotImplementedError, match="cached decode.*two kinds of state"):
        lfm2.FAMILY.decode()
    with pytest.raises(NotImplementedError, match="pipeline parallelism"):
        lfm2.FAMILY.pipeline(config(), FP32)
    with pytest.raises(NotImplementedError, match="embedding_norm"):
        lfm2.FAMILY.head(config(), FP32)
    from neuronx_distributed_training_tpu.tools import convert
    assert "lfm2" not in Path(convert.__file__).read_text()      # HF conversion: not wired
