"""Latent attention with sigmoid-routed experts (``models/kanana.py``): heads
that score over ``qk_nope + qk_rope`` dims and weigh values of another width
off a normed latent, one rotated key shared by all heads, a dense first MLP,
then experts chosen by ``sigmoid score + bias`` whose bias moves by the load
after every optimizer step, of which this program may hold a range, beside
shared experts run as one.  Held against the benchmark's plain reference
(``benchmark/references/kanana.py``, float32, nothing of the program) by the
rungs of ``tests/family_ladder.py``, each of six omissions and an omitted bias
update shown to fail the parity the first holds and the shares of all held
ranges shown to add up to the whole; the flash kernels with ``d_qk != d_v``
held against core attention; the route's selection, weights and gradients
held apart; the accepted families' steps shown untouched."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_ladder
from benchmark.reference import leaf_names
from family_ladder import FP32, at
from neuronx_distributed_training_tpu.models import kanana
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops

#: the published shape at toy widths: the dense layer and a scan of two sparse
#: ones (every kind of layer, and a stack of more than one), 4 heads of 16 + 8 score
#: dims and 16 value dims off a latent of 24, 16 experts of which a token
#: takes 3 and 4 are held, two shared experts
MODEL = dict(
    architecture="kanana", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24, q_lora_rank=None,
    rope_theta=1e6, rope_interleave=True, rms_norm_eps=1e-6, initializer_range=0.02,
    first_k_dense_replace=1, n_routed_experts=16, num_experts_held=[0, 4],
    num_experts_per_tok=3, moe_intermediate_size=32, n_shared_experts=2, n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.448, scoring_func="sigmoid",
    topk_method="noaux_tc", router_bias_update_rate=0.001,
    activations_checkpoint_granularity="full")
SEQ = 32
BIAS = ("layers", "sparse", "mlp", "router", "bias")
SPARSE = "layers/sparse/"
_H, _HEADS = 64, 4
_PROJECTIONS = _H * _HEADS * 24 + _H * (24 + 8) + 24 * _HEADS * (16 + 16) + _HEADS * 16 * _H
_SCORES = _HEADS * (24 + 16) * 4097 / 2

TOY = family_ladder.Toy(
    module=kanana, config_class=kanana.KananaConfig, reference="kanana", model=MODEL, seq=SEQ,
    bias=(BIAS,), omissions=("latent_norm", "rope", "bias", "scale", "renorm", "shared"),
    shapes={
        "layers/dense/mlp/down/w": (1, 128, 64),
        SPARSE + "attn/q/w": (2, 64, 4 * 24), SPARSE + "attn/kv_a/w": (2, 64, 24 + 8),
        SPARSE + "attn/kv_norm/scale": (2, 24), SPARSE + "attn/kv_b/w": (2, 24, 4 * 32),
        SPARSE + "attn/o/w": (2, 64, 64),
        SPARSE + "mlp/experts/down": (2, 4, 32, 64),                   # 4 of 16 held
        SPARSE + "mlp/router/w": (2, 64, 16), SPARSE + "mlp/router/bias": (2, 16),
        SPARSE + "mlp/shared/gate_up/w": (2, 64, 2 * 2 * 32)},         # two as one
    refusals={
        "pipeline": ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
        "tensor": ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
        "context": ({}, {"context_parallel_size": 2}, "context_parallel_size"),
        "held-under-ep": ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
        "held-range": ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
        "query-latent": ({"q_lora_rank": 1536}, {}, "q_lora_rank"),
        "yarn": ({"rope_scaling": {"type": "yarn", "factor": 40}}, {}, "rope_scaling"),
        "groups": ({"n_group": 8, "topk_group": 4}, {}, "n_group"),
        "topk-method": ({"topk_method": "greedy"}, {}, "topk_method"),
        "softmax": ({"scoring_func": "softmax"}, {}, "scoring_func"),
        "bias-never-moves": ({"router_bias_update_rate": 0.0}, {}, "router_bias_update_rate"),
        "bias-rate-missing": ({"router_bias_update_rate": None}, {}, "router_bias_update_rate")},
    # the latent's projections and the held slots: 3 slots a token x 4 of 16
    # held = 0.75 expected slots, + two shared experts
    flops=(({}, {"attention": 3 * 2 * (_PROJECTIONS + _SCORES),
                 "mlp": 6 * _H * 128 + 2 * 6 * _H * 32 * (2 + 0.75),
                 "router": 2 * 2 * _H * 16, "head": 2 * _H * 256}),
           ({"n_routed_experts": 0, "num_experts_held": None}, {"router": 0})),
    shares=(("sparse", 8),),
    summary={"attention_kind": "mla", "mla_dims": [24, 16, 24, 8],
             "moe_experts_held": [0, 4, 16], "moe_score_func": "sigmoid",
             "layer_kinds": {"mlp": {"dense": 1, "sparse": 2}},
             # _HELD_ROWS x the even share, 2 x 32 x 3 x 4 / 16 = 48 rows
             "moe_row_bounds": [int(m * 48) for m in moe_ops._HELD_ROWS]},
    example=("hf_kanana_2_30b_a3b_config.yaml", (), {"data.micro_batch_size": 1},
             {"attention_kind": "mla"}))


class TestLadder(family_ladder.BiasLadder):
    toy = TOY


config = TOY.config


def batch_of(toks):
    return {"input_ids": toks, "labels": toks}


@pytest.fixture(scope="module")
def programs():
    return family_ladder.Programs(TOY)


@pytest.mark.parametrize("policy_name", ["skip_update", "dump_and_continue"])
def test_a_suppressed_step_moves_the_bias_by_nothing(programs, policy_name):
    """Under ``health.policy: skip_update`` a non-finite step leaves the bias
    where it was, bit for bit, as it leaves every other leaf, and the next
    sound step moves it again; under a policy that lets the update through,
    the rule runs with it."""
    from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, init_opt_state
    from neuronx_distributed_training_tpu.telemetry.health import HealthConfig
    from neuronx_distributed_training_tpu.trainer.step import make_train_step

    cfg = config()
    params = programs.weights(0, spread=False)
    step = jax.jit(make_train_step(
        kanana.FAMILY.loss(cfg, FP32), AdamWConfig(), lambda s: 1e-3, FP32,
        health_cfg=HealthConfig(enabled=True, policy=policy_name),
        after_update=kanana.FAMILY.after_update(cfg)))
    clean = {**batch_of(programs.tokens(seed=3)), "loss_mask": jnp.ones((2, SEQ), jnp.float32)}
    poisoned = {**clean, "loss_mask": jnp.full((2, SEQ), jnp.nan, jnp.float32)}
    p1, o1, _ = step(params, init_opt_state(params, FP32, health=True), clean,
                     jax.random.PRNGKey(1))
    assert np.any(np.asarray(at(p1, BIAS)))
    p2, o2, m = step(p1, o1, poisoned, jax.random.PRNGKey(2))
    assert float(m["health/updates_finite"]) == 0.0
    stayed = np.array_equal(np.asarray(at(p2, BIAS)), np.asarray(at(p1, BIAS)))
    assert stayed == (policy_name == "skip_update")
    if stayed:
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
            jax.tree_util.tree_leaves(p2), jax.tree_util.tree_leaves(p1)))
        p3, _, m3 = step(p2, o2, clean, jax.random.PRNGKey(3))
        assert float(m3["health/updates_finite"]) == 1.0
        moved = np.abs(np.asarray(at(p3, BIAS)) - np.asarray(at(p2, BIAS)))
        np.testing.assert_allclose(moved[moved > 0], 0.001, rtol=1e-4)
        assert np.any(moved > 0)


# -- the route ---------------------------------------------------------------


def test_selection_follows_score_plus_bias_weights_and_gradients_the_score():
    cfg = moe_ops.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid",
                            routed_scaling_factor=2.0)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    bias = jnp.zeros((8,)).at[5].set(10.0).at[0].set(-10.0)   # 5 always, 0 never
    probs, idx, logits = moe_ops.route({"w": w, "bias": bias}, x, cfg)
    scores = jax.nn.sigmoid(x @ w)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1))) and not bool(jnp.any(idx == 0))
    want = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(
        2.0 * want / (want.sum(-1, keepdims=True) + 1e-20)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 2.0, rtol=1e-6)
    # with no bias the same scores choose otherwise
    _, plain_idx, _ = moe_ops.route({"w": w, "bias": jnp.zeros((8,))}, x, cfg)
    assert bool(jnp.any(jnp.sort(plain_idx, -1) != jnp.sort(idx, -1)))

    def out(w, bias):
        probs, _, _ = moe_ops.route({"w": w, "bias": bias}, x, cfg)
        return jnp.sum(probs * jnp.arange(2.0))
    d_w, d_bias = jax.grad(out, (0, 1))(w, bias)
    assert float(jnp.linalg.norm(d_w)) > 0 and not np.any(np.asarray(d_bias))
    # the counts are of the chosen slots, and the rule answers to them
    counts = moe_ops.expert_counts(idx, 8)
    assert float(counts.sum()) == 64 and float(counts[5]) == 32 and float(counts[0]) == 0
    moved = moe_ops.bias_update(bias, counts, 0.001)
    assert float(moved[5]) == pytest.approx(10.0 - 0.001) and float(moved[0]) == pytest.approx(-10.0 + 0.001)
    even = moe_ops.bias_update(jnp.zeros((4,)), jnp.full((4,), 7.0), 0.001)
    assert not np.any(np.asarray(even))


def test_the_softmax_route_knows_nothing_of_the_bias():
    """The accepted routes compile as before: no sigmoid, no bias leaf."""
    cfg = moe_ops.MoEConfig(num_experts=8, top_k=2)
    params = moe_ops.init_moe_params(jax.random.PRNGKey(0), 16, 8, cfg)
    assert set(params["router"]) == {"w"}
    text = str(jax.make_jaxpr(lambda p, x: moe_ops.route(p, x, cfg))(
        params["router"], jnp.zeros((4, 16))))
    assert "logistic" not in text and "bias" not in text
    sig = dataclasses.replace(cfg, score_func="sigmoid")
    assert set(moe_ops.init_moe_params(jax.random.PRNGKey(0), 16, 8, sig)["router"]) == {
        "w", "bias"}
    read = moe_ops.MoEConfig.from_config({"num_experts": 8, "scoring_func": "sigmoid",
                                          "router_bias_update_rate": 0.01})
    assert read.score_func == "sigmoid" and read.bias_update_rate == 0.01
    assert moe_ops.MoEConfig.from_config({}).score_func == "softmax"
    # one spelling, the source's
    assert moe_ops.MoEConfig.from_config({"score_func": "sigmoid"}).score_func == "softmax"


# -- the kernels where the score dims are not the value dims ---------------------


@pytest.mark.parametrize("rows", [None, "attention_mask", "segment_ids"])
@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (256, 128), (128, 256)])
def test_flash_with_other_score_dims_than_value_dims_matches_core(d_qk, d_v, rows):
    """The band walk's three kernels against core attention, two heads."""
    family_ladder.flash_matches_core(d_qk + d_v, 2, 2, 2, d_qk, d_v, rows=rows)


def test_what_the_kernels_take_and_how_they_tile_it():
    assert fa.flash_tileable(8192, 8192, 192, 32, 32, d_v=128)
    assert fa.flash_tileable(8192, 8192, 128, 32, 8) and fa.flash_tileable(256, 256, 256, 2, 2)
    assert not fa.flash_tileable(8192, 8192, 192, 32, 32)        # one head dim: whole lanes
    assert not fa.flash_tileable(8192, 8192, 160, 32, 32, d_v=128)
    assert not fa.flash_tileable(8192, 8192, 192, 32, 32, d_v=64)
    # score dims past a lane width halve the key tile; one lane keeps it
    assert fa._block_sizes(8192, 8192, None, None, jnp.bfloat16, 192) == (512, 1024)
    assert fa._block_sizes(8192, 8192, None, None, jnp.bfloat16, 128) == (512, 2048)
    assert fa._block_sizes(8192, 8192, None, None) == (512, 2048)
    # a window no wider than the tile with two head dims keeps the band walk
    q = jnp.zeros((1, 2, 256, 192))
    assert fa._takes_diagonal(q, q, None, None, True, 128, 0, 128, 128)
    assert not fa._one_head_dim(q, jnp.zeros((1, 2, 256, 128)))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    a, b, c = (jax.random.normal(k, (1, 256, 2, d), jnp.float32)
               for k, d in zip(ks, (192, 192, 128)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(fa.flash_attention(a, b, c, sliding_window=128, block_q=128,
                                          block_kv=128, interpret=True)),
            np.asarray(attn_ops.core_attention(a, b, c, sliding_window=128)),
            rtol=2e-4, atol=2e-4)


def test_the_call_says_its_dims_and_its_feeding():
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    q = jnp.zeros((1, 256, 2, 192), jnp.float32)
    v = jnp.zeros((1, 256, 2, 128), jnp.float32)
    with shd.collect_trace_facts() as facts:
        jax.eval_shape(lambda: fa.flash_attention(q, q, v, block_q=128, block_kv=128,
                                                  interpret=True))
        jax.eval_shape(lambda: fa.flash_attention(v, v, v, block_q=128, block_kv=128,
                                                  interpret=True))
    mla, plain = facts["flash_band"]
    assert (mla["d_qk"], mla["d_v"], mla["feed"], mla["walk"]) == (192, 128, "whole", "band")
    assert not {"d_qk", "d_v", "feed"} & set(plain)


def test_interleaved_rope_turns_neighbours(programs):
    reference = programs.reference
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8))
    cos, sin = rope_ops.rope_cos_sin(jnp.arange(8), rope_ops.rope_frequencies(8, theta=1e6))
    mine = rope_ops.apply_rope_interleaved(x, cos, sin)
    theirs = jnp.stack([reference.rotate(x[0, :, h], 8, 1e6) for h in range(2)], axis=1)[None]
    # the reference leaves the pairs where they lie, the program evens first
    np.testing.assert_allclose(np.asarray(mine), np.asarray(jnp.concatenate(
        [theirs[..., 0::2], theirs[..., 1::2]], axis=-1)), rtol=1e-5, atol=1e-6)
    # so a score is the same: <rot q, rot k> either way
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 8))
    np.testing.assert_allclose(
        np.asarray(jnp.sum(mine * rope_ops.apply_rope_interleaved(y, cos, sin), -1)),
        np.asarray(jnp.sum(theirs * jnp.stack(
            [reference.rotate(y[0, :, h], 8, 1e6) for h in range(2)], axis=1)[None], -1)),
        rtol=1e-4, atol=1e-5)


# -- the accepted families' steps are untouched ---------------------------------


@pytest.mark.parametrize("arch, extra", [
    ("llama", {}), ("mixtral", {"moe": {"num_experts": 4, "top_k": 2, "dropless": True}}),
    ("laguna", {"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
                "mlp_layer_types": ["dense", "sparse"]})])
def test_an_accepted_familys_step_has_no_rule_and_lowers_as_without_one(arch, extra):
    """Only this family names a rule beside the optimizer's; a step built with
    none lowers to the text it lowers to without the argument, and holds no
    leaf the optimizer does not know."""
    from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, init_opt_state
    from neuronx_distributed_training_tpu.trainer.step import make_train_step

    family, cfg = resolve({"model": {
        "architecture": arch, "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "activations_checkpoint_granularity": None, **extra}})
    assert family.after_update(cfg) is None
    # shapes are all a lowering needs: nothing is drawn
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg, FP32))
    assert not any("bias" in n for n in leaf_names(params))
    state = jax.eval_shape(lambda: init_opt_state(params, FP32))
    rows = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"input_ids": rows, "labels": rows}
    loss = family.loss(cfg, FP32)

    def lowered(**kw):
        step = make_train_step(loss, AdamWConfig(), lambda s: 1e-3, FP32, **kw)
        return jax.jit(step).lower(params, state, batch, family_ladder.key_of(0)).as_text()

    assert lowered() == lowered(after_update=None)


