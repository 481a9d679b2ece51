"""The mixed stack (``models/laguna.py``): full and window attention layers
with their own head counts, rotary embeddings and a per-head gate, a dense
first MLP, then routed experts of which this program may hold a range, beside
a shared expert.  Held against the benchmark's plain reference
(``benchmark/references/laguna.py``, float32, nothing of the program) by the
rungs of ``tests/family_ladder.py``, each of seven omissions shown to fail the
parity the first holds and the shares of the experts shown to add up to the
whole; the flash kernels held against core attention at the two GQA groups;
the held rows' bound shown to change nothing but the path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_ladder
from family_ladder import FP32, worst_gap
from neuronx_distributed_training_tpu.models import laguna
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops

ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                           "original_max_position_embeddings": 8192, "beta_slow": 1,
                           "beta_fast": 32, "attention_factor": 1.4852030263919618,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
#: the published shape at toy widths: 1 + 4 layers, head counts that differ by
#: kind over 2 kv heads, 16 experts of which a token takes 4 and 4 are held
MODEL = dict(
    architecture="laguna", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, sliding_window=8, initializer_range=0.02,
    layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer={"full_attention": 4, "sliding_attention": 6},
    rope_parameters=ROPE, num_experts=16, num_experts_held=[0, 4], num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    moe_routed_scaling_factor=2.5, norm_topk_prob=True, router_aux_loss_coef=0.001,
    activations_checkpoint_granularity="full")
SEQ = 32
_H, _D = 64, 16
_FULL = 2 * _H * (4 + 4) * _D + 2 * 4 * _D * _H + 2 * _H * 4 + 4 * 4 * _D * (4097 / 2)
_KEYS = (8 * 9 / 2 + (4096 - 8) * 8) / 4096          # the window caps a token's keys
_SLIDING = 2 * _H * (6 + 4) * _D + 2 * 6 * _D * _H + 2 * _H * 6 + 4 * 6 * _D * _KEYS

TOY = family_ladder.Toy(
    module=laguna, config_class=laguna.LagunaConfig, reference="laguna", model=MODEL, seq=SEQ,
    omissions=("head_gate", "window", "partial_rotary", "attention_factor", "scale", "renorm",
               "shared"),
    # the two kinds' projections differ in shape; the held experts are 4 of 16
    shapes={"layers/full_dense/attn/qkv/w": (1, 64, (4 + 4) * 16),
            "layers/sliding_sparse/attn/qkv/w": (3, 64, (6 + 4) * 16),
            "layers/full_sparse/attn/qkv/w": (1, 64, (4 + 4) * 16),
            "layers/full_sparse/mlp/experts/down": (1, 4, 32, 64),
            "layers/full_sparse/mlp/router/w": (1, 64, 16)},
    refusals={
        "pipeline": ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
        "tensor": ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
        "context": ({}, {"context_parallel_size": 2}, "context_parallel_size"),
        "held-under-ep": ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
        "held-range": ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
        "heads-differ-in-a-kind": ({"num_attention_heads_per_layer": [4, 6, 6, 5, 4]}, {},
                                   "num_attention_heads_per_layer"),
        "heads-over-kv": ({"num_attention_heads_per_layer": {"sliding_attention": 5}}, {},
                          "num_key_value_heads"),
        "short-list": ({"layer_types": ["full_attention"] * 3}, {}, "layer_types"),
        "unknown-mlp": ({"mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "moe"]}, {},
                        "mlp_layer_types"),
        "no-window": ({"sliding_window": None}, {}, "sliding_window"),
        "gating": ({"gating": "per-element"}, {}, "gating"),
        "rope-type": ({"rope_parameters": {**ROPE, "full_attention": {"rope_type": "llama3"}}},
                      {}, "rope_type")},
    # 4 slots a token x 4 of 16 held = 1 expected slot, + the shared expert
    flops=(({}, {"attention": 2 * _FULL + 3 * _SLIDING,
                 "mlp": 6 * _H * 128 + 4 * 6 * _H * (32 + 1 * 32),
                 "router": 4 * 2 * _H * 16, "head": 2 * _H * 256}),
           ({"num_experts_held": None}, {"mlp": 6 * _H * 128 + 4 * 6 * _H * (32 + 4 * 32)})),
    shares=(("full_sparse", 2), ("full_sparse", 4)),
    summary={"layer_kinds": {"attention": {"full_attention": 2, "sliding_attention": 3},
                             "mlp": {"dense": 1, "sparse": 4}},
             "moe_experts_held": [0, 4, 16],
             # _HELD_ROWS x the even share of 2 x 32 x 4 x 4 / 16 = 64 rows
             "moe_row_bounds": [int(m * 64) for m in moe_ops._HELD_ROWS]},
    example=("hf_laguna_s_2_1_config.yaml",
             ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "rope_parameters"),
             {"model.num_hidden_layers": 9,
              "model.num_attention_heads_per_layer": [4, 6, 6, 6] * 12},
             {"layer_kinds": {"attention": {"full_attention": 3, "sliding_attention": 6},
                              "mlp": {"dense": 1, "sparse": 8}}}))


@pytest.fixture(scope="module")
def programs():
    return family_ladder.Programs(TOY)


class TestLadder(family_ladder.Ladder):
    toy = TOY

    def test_the_toy_runs_held_rows_are_a_share_of_the_even_share(self, trained):
        for r in trained["logged"]:
            assert r["moe/held_rows_share"] == pytest.approx(
                r["moe/held_rows"] / (2 * SEQ * 4 * 4 / 16), rel=1e-6)
            assert 0 < r["router_aux_loss"] < 0.01


config = TOY.config


# -- the held rows' bound -------------------------------------------------------


@pytest.mark.parametrize("pair, operand, by_slices", [
    ((0.125, 0.25), 0.25, True),
    ((0.375, 0.625), 0.625, True),
    ((0.625, 3.0), 3.0, False),
    ((0.625, 4.0), 4.0, False),    # 4 x the even share is every row: never a second slice
    ((3.0, 3.5), 3.0, False),
], ids=["many-slices", "two-slices", "between-the-two", "between-the-two-no-slices",
        "under-the-narrow"])
def test_a_step_past_the_bound_gives_the_same_loss_and_gradients(
        programs, monkeypatch, pair, operand, by_slices):
    """Whatever way a step takes (the one pass over the narrow operand, one
    slice of the wide one, or several), the loss and every gradient are those
    of the family's program at ``_HELD_ROWS`` as shipped, and the counters say
    which operand the step had and whether it took more than one slice."""
    cfg = config()
    params, toks = programs.weights(5), programs.tokens(6)
    (under, under_aux), under_grads = programs.program(params, toks)
    share = float(under_aux["moe/held_rows_share"])
    assert 0.625 < share <= moe_ops._HELD_ROWS[0]
    assert float(under_aux["moe/held_operand"]) == moe_ops._HELD_ROWS[0]
    monkeypatch.setattr(moe_ops, "_HELD_ROWS", pair)    # read when traced: a program of its own
    with jax.default_matmul_precision("highest"):
        (past, past_aux), past_grads = jax.jit(jax.value_and_grad(
            lambda p: laguna.forward(p, {"input_ids": toks, "labels": toks}, cfg, FP32),
            has_aux=True))(params)
    assert float(under_aux["moe/row_bound"]) == 0.0
    assert float(past_aux["moe/row_bound"]) == by_slices
    assert float(past_aux["moe/held_operand"]) == operand
    assert float(past_aux["moe/held_rows"]) == float(under_aux["moe/held_rows"])
    assert float(past_aux["moe/held_rows_share"]) == share
    assert float(past) == pytest.approx(float(under), rel=1e-6)
    assert worst_gap(past_grads, under_grads) < 2e-5


@pytest.mark.parametrize("top_k", [4, 6, 10])
@pytest.mark.parametrize("side", [-1, 1], ids=["just-under", "just-over"])
@pytest.mark.parametrize("tier", [0, 1], ids=["narrow", "wide"])
def test_the_bound_changes_the_path_and_not_the_result(monkeypatch, top_k, side, tier):
    """Rows held one short of a bound and one past it (the narrow bound: the
    one pass over the narrow operand, then one slice of the wide one; the wide
    bound: that slice, then two): the block's output and every gradient are
    those of one pass over all ``T * k`` rows, no branch."""
    tokens, experts_n, held, h, f = 32, 32, 8, 16, 24
    rng = np.random.default_rng(top_k)
    # a quarter of the experts held and their share of the choices spread
    # evenly over the tokens: the bound exactly; then one more or less
    even = tokens * top_k * held / experts_n
    rows = int(moe_ops._HELD_ROWS[tier] * even)
    take = np.full(tokens, rows // tokens)
    take[:rows % tokens] += 1
    take[-1] += side
    idx = jnp.asarray(np.stack([np.concatenate([
        rng.permutation(held)[:n], held + rng.permutation(experts_n - held)[:top_k - n]])
        for n in take]), jnp.int32)
    cfg = moe_ops.MoEConfig(num_experts=experts_n, top_k=top_k, experts_held=(0, held))
    keys = jax.random.split(jax.random.PRNGKey(top_k), 4)
    experts = {"gate_up": jax.random.normal(keys[0], (held, h, 2 * f)) * 0.2,
               "down": jax.random.normal(keys[1], (held, f, h)) * 0.2}
    x = jax.random.normal(keys[2], (tokens, h))
    probs = jax.nn.softmax(jax.random.normal(keys[3], (tokens, top_k)))

    def run():
        def loss(experts, x, probs):
            y, stats = moe_ops._dropless_held(experts, x, probs, idx, cfg,
                                              compute_dtype=jnp.float32)
            return jnp.sum(jnp.sin(y)), stats
        with jax.default_matmul_precision("highest"):
            f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
            return f(experts, x, probs), f.lower(experts, x, probs).as_text()

    ((ours, stats), grads), text = run()
    took = min(tier + (side > 0), 1)
    assert float(stats["moe/held_rows"]) == rows + side
    assert float(stats["moe/held_operand"]) == moe_ops._HELD_ROWS[took]
    assert float(stats["moe/row_bound"]) == (tier == 1 and side > 0)
    assert text.count("stablehlo.case") == 2       # a branch a pass
    monkeypatch.setattr(moe_ops, "_HELD_ROWS", (4.0, 4.0))
    ((wide, wide_stats), wide_grads), wide_text = run()
    assert float(wide_stats["moe/row_bound"]) == 0.0
    assert float(wide_stats["moe/held_operand"]) == 4.0
    assert "stablehlo.case" not in wide_text
    assert float(ours) == pytest.approx(float(wide), rel=1e-6)
    assert worst_gap(grads, wide_grads) < 2e-5


@pytest.mark.parametrize("held, held_tokens, bounds, operand, by_slices, branch", [
    (4, 64, [96, 128], 128, True, 1),     # every row held, 4 x the even share: two slices
    (4, 24, [96, 128], 96, False, 1),     # the narrow bound exactly: its one pass
    (4, 25, [96, 128], 128, False, 1),    # four rows past it: one slice of the wide one
    (4, 32, [96, 128], 128, False, 1),    # the wide bound exactly
    (4, 33, [96, 128], 128, True, 1),     # four rows past it: a second slice
    (8, 64, [192, 256], 256, False, 1),   # 2 x the even share of 128 is every row: one slice
    (12, 64, [256, 256], 256, False, 0),  # 1.5 x the even share of 192 is over the 256 rows
], ids=["all-rows-held", "at-the-narrow-bound", "past-the-narrow-bound", "at-the-wide-bound",
        "past-the-wide-bound", "wide-is-every-row", "never-over-the-rows"])
def test_the_bound_is_a_multiple_of_the_even_share_and_never_over_the_rows(
        held, held_tokens, bounds, operand, by_slices, branch):
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    z = jnp.zeros((64, 8))
    # a token's four choices: the first four held experts, or four held by none
    idx = jnp.where(jnp.arange(64)[:, None] < held_tokens, jnp.arange(4)[None], 12 + jnp.arange(4))
    probs = jnp.full((64, 4), 0.25)
    experts = {"gate_up": jnp.zeros((held, 8, 16)), "down": jnp.zeros((held, 8, 8))}
    cfg = moe_ops.MoEConfig(num_experts=16, top_k=4, experts_held=(0, held))

    def block(experts, z, probs, idx):
        return moe_ops._dropless_held(experts, z, probs, idx, cfg, compute_dtype=jnp.float32)

    with shd.collect_trace_facts() as traced:
        _, stats = block(experts, z, probs, idx)
    even = 64 * 4 * held / 16
    assert traced["moe_row_bounds"] == bounds == [
        min(int(m * even), 256) for m in moe_ops._HELD_ROWS]
    assert float(stats["moe/held_rows"]) == 4 * held_tokens
    assert float(stats["moe/held_rows_share"]) == pytest.approx(4 * held_tokens / even)
    assert float(stats["moe/held_operand"]) == pytest.approx(operand / even)
    assert float(stats["moe/row_bound"]) == by_slices
    # a narrow operand that holds every case lowers with no branch
    text = jax.jit(block).lower(experts, z, probs, idx).as_text()
    assert text.count("stablehlo.case") == branch
    with pytest.raises(NotImplementedError, match="experts_held"):
        moe_ops._dropless_experts(experts, z, probs, idx, cfg, compute_dtype=jnp.float32,
                                  expert_axis="expert")


# -- the kernels at this model's shapes -----------------------------------------


@pytest.mark.parametrize("nh, window", [(12, 64), (18, 64), (12, None)],
                         ids=["group-6-window", "group-9-window", "group-6-causal"])
def test_flash_matches_core_at_gqa_groups_of_6_and_9(nh, window):
    """2 kv heads shared by groups of 6 and 9 query heads, a window of 64 under
    key tiles of 128 (narrower than the tile, as 512 is under the default 2048)."""
    family_ladder.flash_matches_core(nh, 1, nh, 2, 128, 128, window=window)


# -- the rotary embeddings -------------------------------------------------------


def test_yarn_frequencies_and_the_partial_rotation():
    full = ROPE["full_attention"]
    inv = rope_ops.rope_frequencies(128, theta=500000, partial_rotary_factor=0.5, yarn=full)
    assert inv.shape == (32,)                      # 64 of 128 dims rotate
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    # the fastest dims keep their frequency, the slowest are divided by 128
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-12)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 128, rtol=1e-12)
    assert np.all(np.diff(inv) < 0)
    assert np.all(inv <= plain * (1 + 1e-12)) and np.all(inv >= plain / 128 * (1 - 1e-12))
    # between the dims of 32 turns and of 1 turn in 8192 positions (9 and 18) the blend
    assert np.all(inv[10:18] < plain[10:18] * 0.999) and np.all(inv[10:18] > plain[10:18] / 128 * 1.001)
    assert rope_ops.yarn_attention_factor(full) == pytest.approx(0.1 * np.log(128) + 1, rel=1e-12)
    # without the new arguments the table is the old one, bit for bit
    np.testing.assert_array_equal(
        rope_ops.rope_frequencies(128, theta=1e4), 1.0 / 1e4 ** (np.arange(0, 128, 2) / 128))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    cos, sin = rope_ops.rope_cos_sin(jnp.arange(8), inv, scale=1.4852030263919618)
    out = rope_ops.apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(out[..., 64:]), np.asarray(x[..., 64:]))
    # position 0: cos = factor, sin = 0
    np.testing.assert_allclose(np.asarray(out[:, 0, :, :64]),
                               1.4852030263919618 * np.asarray(x[:, 0, :, :64]), rtol=1e-6)


# -- the stack's plan ------------------------------------------------------------


def test_the_published_stack_is_three_scans():
    s, f = ("sliding_attention", "sparse"), ("full_attention", "sparse")
    kinds = [("full_attention", "dense")] + [s] * 3 + ([f] + [s] * 3) * 11
    assert laguna.stack_plan(kinds) == [
        ("run", ("full_attention", "dense"), 1),
        ("periods", 11, ((s, 3), (f, 1))),
        ("run", s, 3)]
    assert laguna.stack_plan(kinds[:5]) == [
        ("run", ("full_attention", "dense"), 1), ("run", s, 3), ("run", f, 1)]
    assert laguna.stack_plan([f] * 6) == [("run", f, 6)]


def test_a_stack_of_two_periods_runs_as_the_layers_one_by_one(programs):
    """1 + 8 layers (a prefix and two periods): the scan over periods takes
    each kind's layers from its stack in layer order."""
    deep = {**MODEL, "num_hidden_layers": 9,
            "layer_types": MODEL["layer_types"] + ["sliding_attention"] * 3 + ["full_attention"],
            "mlp_layer_types": MODEL["mlp_layer_types"] + ["sparse"] * 4}
    cfg = laguna.LagunaConfig.from_config(deep, {})
    assert [seg[0] for seg in laguna.stack_plan(cfg.kinds)] == ["run", "periods"]
    found = programs.against(weights=8, tokens=3, model=deep)
    assert found["loss"] == pytest.approx(found["ref_loss"], rel=2e-6)
    assert found["worst"] < 5e-5


def test_a_depth_under_the_lists_runs_the_leading_layers():
    cfg = laguna.LagunaConfig.from_config({**MODEL, "num_layers": 2}, {})
    assert cfg.kinds == (("full_attention", "dense"), ("sliding_attention", "sparse"))
    listed = laguna.LagunaConfig.from_config(
        {**MODEL, "num_attention_heads_per_layer": [4, 6, 6, 6, 4]}, {})
    assert listed.heads_by_type == config().heads_by_type


def test_window_layers_take_key_tiles_no_wider_than_their_window():
    """The flash key tile is the program's to choose, from the window it can
    see: 512 at the published window under the default 2048, a lane of 128 at
    least, the config's ``flash_block_kv`` where that is narrower; full layers
    keep the config's."""
    assert config().block_kv("sliding_attention") == 128           # window 8
    assert config(sliding_window=512).block_kv("sliding_attention") == 512
    assert config(sliding_window=600).block_kv("sliding_attention") == 640
    assert config(sliding_window=8192).block_kv("sliding_attention") == 2048
    narrow = config(sliding_window=512, fusions={"flash_attention": True, "flash_block_kv": 256})
    assert narrow.block_kv("sliding_attention") == 256 == narrow.block_kv("full_attention")
    assert config().block_kv("full_attention") is None
