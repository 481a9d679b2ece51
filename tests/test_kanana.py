"""Latent attention with sigmoid-routed experts (``models/kanana.py``): heads
that score over ``qk_nope + qk_rope`` dims and weigh values of another width
off a normed latent, one rotated key shared by all heads, a dense first MLP,
then experts chosen by ``sigmoid score + bias`` whose bias moves by the load
after every optimizer step, of which this program may hold a range, beside
shared experts run as one.  Held against the benchmark's plain reference
(``benchmark/references/kanana.py``, float32, nothing of the program); each of
six omissions and an omitted bias update shown to fail the parity the first test holds; the flash
kernels with ``d_qk != d_v`` held against core attention; the route's
selection, weights and gradients held apart; the shares of all held ranges
shown to add up to the whole; the accepted families' steps shown untouched."""

import dataclasses
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check as checks
from benchmark.reference import leaf_names
from neuronx_distributed_training_tpu.models import kanana
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ROOT = Path(__file__).resolve().parents[1]
#: the published shape at toy widths: 1 + 3 layers, 4 heads of 16 + 8 score
#: dims and 16 value dims off a latent of 24, 16 experts of which a token
#: takes 3 and 4 are held, two shared experts
MODEL = dict(
    architecture="kanana", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24, q_lora_rank=None,
    rope_theta=1e6, rope_interleave=True, rms_norm_eps=1e-6, initializer_range=0.02,
    first_k_dense_replace=1, n_routed_experts=16, num_experts_held=[0, 4],
    num_experts_per_tok=3, moe_intermediate_size=32, n_shared_experts=2, n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.448, scoring_func="sigmoid",
    topk_method="noaux_tc", router_bias_update_rate=0.001,
    activations_checkpoint_granularity="full")
OPTIM = {"lr": 1e-3, "weight_decay": 0.1, "betas": [0.9, 0.95], "eps": 1e-8,
         "sched": {"warmup_steps": 0, "max_steps": 100}}
FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
SEQ = 32
BIAS = ("layers", "sparse", "mlp", "router", "bias")


@pytest.fixture(scope="module")
def reference():
    return importlib.import_module("benchmark.references.kanana")


def config(**over):
    return kanana.KananaConfig.from_config({**MODEL, **over}, {})


def tokens(seed=1, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, MODEL["vocab_size"])


def batch_of(toks):
    return {"input_ids": toks, "labels": toks}


def at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def spread(params, seed=9):
    """Norm scales moved off their initial 1, every other weight grown
    fivefold and the selection bias off 0 by about the gap between two
    experts' scores, so that attention is far from uniform and a norm, a
    rotation or the bias left out shows."""
    def leaf(path, x):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), sum(map(ord, name)))
        if "norm" in name:
            return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
        if name.endswith("router/bias"):
            return 0.1 * jax.random.normal(key, x.shape, x.dtype)
        return x * (1.0 if "embed" in name or "lm_head" in name else 5.0)
    return jax.tree_util.tree_map_with_path(leaf, params)


def value_and_grads(fn, params):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(fn))(params)


def worst_gap(a, b):
    """Largest relative gap of two gradient trees, leaf by leaf."""
    return max(float(jnp.linalg.norm(x - y) / (jnp.linalg.norm(y) + 1e-30))
               for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# -- against the reference ----------------------------------------------------


def test_the_seeded_weights_are_the_references_leaf_for_leaf(reference):
    cfg = config()
    key = jax.random.PRNGKey(11)
    mine, theirs = kanana.init_params(key, cfg, FP32), reference.init_params(MODEL, key)
    assert reference.leaf_names(mine) == reference.leaf_names(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    assert sorted(mine["layers"]) == ["dense", "sparse"]
    attn = mine["layers"]["sparse"]["attn"]
    assert attn["q"]["w"].shape == (3, 64, 4 * 24) and attn["kv_a"]["w"].shape == (3, 64, 24 + 8)
    assert attn["kv_norm"]["scale"].shape == (3, 24)
    assert attn["kv_b"]["w"].shape == (3, 24, 4 * 32) and attn["o"]["w"].shape == (3, 64, 64)
    mlp = mine["layers"]["sparse"]["mlp"]
    assert mlp["experts"]["down"].shape == (3, 4, 32, 64)           # 4 of 16 held
    assert mlp["router"]["w"].shape == (3, 64, 16) and mlp["router"]["bias"].shape == (3, 16)
    assert mlp["shared"]["gate_up"]["w"].shape == (3, 64, 2 * 2 * 32)   # two as one
    assert not np.any(np.asarray(mlp["router"]["bias"]))
    specs = kanana.param_specs(cfg)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert jax.tree_util.tree_structure(specs, is_leaf=is_spec) == jax.tree_util.tree_structure(mine)
    for spec, leaf in zip(jax.tree_util.tree_leaves(specs, is_leaf=is_spec),
                          jax.tree_util.tree_leaves(mine)):
        assert len(spec) == leaf.ndim


@pytest.mark.parametrize("granularity", [None, "selective", "full"])
def test_loss_and_every_gradient_match_the_reference_in_float32(reference, granularity):
    cfg = config(activations_checkpoint_granularity=granularity)
    params = spread(kanana.init_params(jax.random.PRNGKey(3), cfg, FP32))
    toks = tokens()
    loss, grads = value_and_grads(
        lambda p: kanana.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(MODEL)
    ref_loss, ref_grads = value_and_grads(
        lambda p: reference.microbatch_loss(p, toks, c)[0], params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for name, g, r in zip(reference.leaf_names(grads), jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(g - r)) <= 2e-5 * float(jnp.linalg.norm(r)), name
    # the bias steers and is never weighed: its gradient is exactly zero on both sides
    assert not np.any(np.asarray(at(grads, BIAS))) and not np.any(np.asarray(at(ref_grads, BIAS)))
    # and the loads the rule reads are the reference's, expert for expert
    _, aux = kanana.forward(params, batch_of(toks), cfg, FP32)
    np.testing.assert_array_equal(np.asarray(aux[kanana.COUNTS]),
                                  np.asarray(reference.microbatch_loss(params, toks, c)[1]))
    assert float(jnp.sum(aux[kanana.COUNTS])) == 3 * 2 * SEQ * 3        # layers x tokens x k


@pytest.fixture(scope="module")
def trained(reference, tmp_path_factory):
    """``Trainer.from_config(cfg).fit()`` in float32, three steps of two
    micro-batches, beside ``reference.run`` on the same rows."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.data.loader import DataModule
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    seed, rows = 5, 4
    steps = [np.asarray(tokens(seed=100 + k, rows=rows)) for k in range(3)]

    class Rows(DataModule):
        def fetch_rows(self, idx):
            return {"input_ids": np.stack([steps[i // rows][i % rows] for i in idx])}

    cfg = load_config({
        "seed": seed, "model": {**MODEL, "optim": {"name": "adamw_fp32OptState", **OPTIM}},
        "distributed_strategy": {"tensor_model_parallel_size": 1},
        "data": {"global_batch_size": rows, "micro_batch_size": 2, "seq_length": SEQ},
        "trainer": {"max_steps": 3, "log_every_n_steps": 1, "gradient_clip_val": 1.0},
        "exp_manager": {"exp_dir": str(tmp_path_factory.mktemp("kanana")), "name": "kanana"},
        "precision": {"type": "fp32"}})
    trainer = Trainer.from_config(cfg, data_module=Rows(1 << 10, rows),
                                  devices=jax.devices()[:1], enable_checkpointing=False)
    with jax.default_matmul_precision("highest"):
        trainer.fit()
    log_dir = Path(trainer.exp.log_dir)
    logged = [json.loads(line) for line in open(log_dir / "metrics.jsonl")]
    ref = reference.run(MODEL, OPTIM, 1.0, [s.reshape(2, 2, SEQ) for s in steps], seed)
    return trainer, logged, json.load(open(log_dir / "run_summary.json")), ref, seed


def test_three_steps_match_the_reference_in_float32(reference, trained):
    """The losses of three steps and the parameters' change, leaf by leaf, the
    selection bias among them: three steps of the rule on both sides."""
    trainer, logged, summary, ref, seed = trained
    assert [r["loss"] for r in logged] == pytest.approx(ref["loss"], rel=1e-5)
    dparam = checks.parameter_change_norms(reference, trainer.params, MODEL, seed)
    gaps = checks.leaf_gaps(dparam, ref["dparam"])
    assert max(gaps.values()) < 2e-4, max(gaps, key=gaps.get)
    assert dparam["layers/sparse/mlp/router/bias"] > 0.001 * np.sqrt(3 * 16) * 0.5
    grad1 = checks.first_gradient_norms(reference, trainer.opt_state, 0.9)
    assert set(grad1) == set(ref["grad1"])
    for r in logged:
        assert r["moe/row_bound"] == 0.0 and r["moe/held_rows"] > 0
        assert 1.0 <= r["moe/load_max_share"] < 16 / 3
        assert kanana.COUNTS not in r
    assert [r["moe/bias_abs_max"] for r in logged] == pytest.approx([0.0, 0.001, 0.002])
    assert summary["attention_kind"] == "mla" and summary["mla_dims"] == [24, 16, 24, 8]
    assert summary["moe_experts_held"] == [0, 4, 16] and summary["moe_score_func"] == "sigmoid"
    assert summary["layer_kinds"] == {"mlp": {"dense": 1, "sparse": 3}}
    # _HELD_ROWS x the even share, 2 x 32 x 3 x 4 / 16 = 48 rows
    assert summary["moe_row_bounds"] == [int(moe_ops._HELD_ROWS * 48)] == [144]


def test_the_bias_moves_by_the_rule_and_by_nothing_of_adamws(trained):
    """After three steps every element of the bias is a whole number of steps
    of 0.001 (no decay, no moment's step mixed in) and the optimizer's moments
    for it are exactly zero."""
    trainer, *_ = trained
    bias = np.asarray(at(trainer.params, BIAS), np.float64)
    steps = bias / 0.001
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert set(np.round(steps).astype(int).ravel()) <= {-3, -2, -1, 0, 1, 2, 3}
    assert np.any(np.round(steps) != 0)
    for moment in ("mu", "nu"):
        assert not np.any(np.asarray(at(trainer.opt_state[moment], BIAS)))


@pytest.mark.parametrize("policy_name", ["skip_update", "dump_and_continue"])
def test_a_suppressed_step_moves_the_bias_by_nothing(policy_name):
    """Under ``health.policy: skip_update`` a non-finite step leaves the bias
    where it was, bit for bit, as it leaves every other leaf, and the next
    sound step moves it again; under a policy that lets the update through,
    the rule runs with it."""
    from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, init_opt_state
    from neuronx_distributed_training_tpu.telemetry.health import HealthConfig
    from neuronx_distributed_training_tpu.trainer.step import make_train_step

    cfg = config()
    params = kanana.init_params(jax.random.PRNGKey(0), cfg, FP32)
    step = jax.jit(make_train_step(
        kanana.FAMILY.loss(cfg, FP32), AdamWConfig(), lambda s: 1e-3, FP32,
        health_cfg=HealthConfig(enabled=True, policy=policy_name),
        after_update=kanana.FAMILY.after_update(cfg)))
    clean = {**batch_of(tokens(seed=3)), "loss_mask": jnp.ones((2, SEQ), jnp.float32)}
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


# -- the comparison is tight enough: what is left out shows ---------------------

OMISSIONS = ["latent_norm", "rope", "bias", "scale", "renorm", "shared"]


@pytest.fixture(scope="module")
def parity(reference):
    """The program's float32 loss and gradients on spread-out weights, and a
    comparison of them with the reference's with something left out."""
    cfg = config()
    params = spread(kanana.init_params(jax.random.PRNGKey(7), cfg, FP32))
    toks = tokens(seed=4)
    loss, grads = value_and_grads(
        lambda p: kanana.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(MODEL)

    def against(left_out=()):
        ref_loss, ref_grads = value_and_grads(
            lambda p: reference.microbatch_loss(p, toks, c, left_out=left_out)[0], params)
        return abs(float(loss) - float(ref_loss)), worst_gap(grads, ref_grads)

    return against


def test_nothing_left_out_is_parity(parity):
    loss_gap, grad_gap = parity()
    assert loss_gap < 1e-5 and grad_gap < 5e-5


@pytest.mark.parametrize("omission", OMISSIONS)
def test_an_omission_fails_parity(parity, omission):
    """Each part of the layer that the configuration states, left out of the
    reference alone, moves a gradient leaf by a hundred times the rounding."""
    loss_gap, grad_gap = parity(left_out=(omission,))
    assert grad_gap > 5e-3, (omission, loss_gap, grad_gap)


def test_an_omitted_bias_update_shows_in_the_parameters_change(reference):
    """The rule left out of the reference's step: the bias's change reads 0
    there and the comparison 1 (a state left unchanged)."""
    steps = [np.asarray(tokens(seed=100 + k, rows=2))[None] for k in range(3)]
    # the cell's regime: a small rate under its warm-up, so that the weights
    # move by less than the bias's steps of 0.001
    optim = {**OPTIM, "lr": 1e-5, "sched": {"warmup_steps": 100, "max_steps": 1000}}
    with_rule = reference.run(MODEL, optim, 1.0, steps, 5)
    without = reference.run(MODEL, optim, 1.0, steps, 5, left_out=("bias_update",))
    name = "layers/sparse/mlp/router/bias"
    assert without["dparam"][name] == 0.0 < with_rule["dparam"][name]
    assert with_rule["grad1"][name] == 0.0
    gaps = checks.leaf_gaps(without["dparam"], with_rule["dparam"])
    assert gaps[name] == pytest.approx(1.0) and max(
        v for k, v in gaps.items() if k != name) < 0.1


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


# -- the experts' shares add up -------------------------------------------------


def test_the_shares_of_all_held_ranges_and_the_shared_experts_once_make_the_layer(reference):
    """A sparse layer's MLP output with all 16 experts in one program equals
    the sum over 8 chips of what each makes of the 2 experts it holds, plus
    the shared experts counted once; and both equal the uncut reference."""
    cfg = config(num_experts_held=None)
    layer = jax.tree_util.tree_map(
        lambda a: a[0], spread(kanana.init_params(jax.random.PRNGKey(2), cfg, FP32))
        ["layers"]["sparse"]["mlp"])
    assert layer["experts"]["gate_up"].shape[0] == 16
    z = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)

    def block(params, held):
        moe = dataclasses.replace(cfg.moe, experts_held=held)
        with jax.default_matmul_precision("highest"):
            return moe_ops.moe_block(params, z, moe, compute_dtype=jnp.float32)

    whole, whole_aux = block(layer, None)
    shares, per = 8, 2
    parts = [block({"router": layer["router"], "experts": jax.tree_util.tree_map(
        lambda a, s=s: a[s * per:(s + 1) * per], layer["experts"])},
        (s * per, (s + 1) * per)) for s in range(shares)]
    shared = moe_ops._shared_expert(layer["shared"], z, jnp.float32)
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts) + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)
    # every chip routes over all 16 and counts the same loads
    for _, aux in parts:
        np.testing.assert_array_equal(np.asarray(aux["expert_counts"]),
                                      np.asarray(whole_aux["expert_counts"]))
    c = reference.dims(MODEL)
    with jax.default_matmul_precision("highest"):
        uncut, loads = reference.expert_block(layer, z.reshape(-1, 64), c,
                                              reference.plain._matmul(None), held=(0, 16))
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, 64), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(whole_aux["expert_counts"]))


# -- the kernels where the score dims are not the value dims ---------------------


@pytest.mark.parametrize("rows", [None, "attention_mask", "segment_ids"])
@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (256, 128), (128, 256)])
def test_flash_with_other_score_dims_than_value_dims_matches_core(d_qk, d_v, rows):
    """The band walk's three kernels, interpret mode, against core attention:
    forward and all three gradients, causal, also under a padding mask and
    packed segments."""
    ks = jax.random.split(jax.random.PRNGKey(d_qk + d_v), 4)
    b, s, nh = 2, 256, 2
    q = jax.random.normal(ks[0], (b, s, nh, d_qk), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, nh, d_qk), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, nh, d_v), jnp.float32)
    ct = jax.random.normal(ks[3], (b, s, nh, d_v), jnp.float32)
    mask = jnp.arange(s)[None, :] < jnp.array([[s], [s - 70]])
    segments = jnp.stack([jnp.arange(s) // 100, jnp.arange(s) // 64])
    kw = {"attention_mask": mask} if rows == "attention_mask" else (
        {"segment_ids": segments} if rows == "segment_ids" else {})
    bias = None
    if rows == "attention_mask":
        bias = attn_ops.padding_mask_bias(mask)
    if rows == "segment_ids":
        bias = attn_ops.segment_mask_bias(segments)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * ct * (
            mask[:, :, None, None] if rows == "attention_mask" else 1.0))

    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=128, block_kv=128, interpret=True, **kw)
    core = lambda q, k, v: attn_ops.core_attention(q, k, v, causal=True, bias=bias)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        assert out.shape == (b, s, nh, d_v)
        keep = mask[:, :, None, None] if rows == "attention_mask" else True
        np.testing.assert_allclose(np.asarray(jnp.where(keep, out, 0)),
                                   np.asarray(jnp.where(keep, core(q, k, v), 0)),
                                   rtol=2e-4, atol=2e-4)
        for a, c in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                        jax.grad(loss(core), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-3)


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


def test_interleaved_rope_turns_neighbours(reference):
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
    params = family.init_params(jax.random.PRNGKey(0), cfg, FP32)
    assert not any("bias" in n for n in leaf_names(params))
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32), "labels": jnp.zeros((2, 16), jnp.int32)}
    loss = family.loss(cfg, FP32)

    def lowered(**kw):
        step = make_train_step(loss, AdamWConfig(), lambda s: 1e-3, FP32, **kw)
        return jax.jit(step).lower(params, init_opt_state(params, FP32), batch,
                                   jax.random.PRNGKey(0)).as_text()

    assert lowered() == lowered(after_update=None)


# -- what is not wired is refused by name ---------------------------------------


@pytest.mark.parametrize("model, ds, named", [
    ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
    ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
    ({}, {"context_parallel_size": 2}, "context_parallel_size"),
    ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
    ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
    ({"q_lora_rank": 1536}, {}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, {}, "rope_scaling"),
    ({"n_group": 8, "topk_group": 4}, {}, "n_group"),
    ({"topk_method": "greedy"}, {}, "topk_method"),
    ({"scoring_func": "softmax"}, {}, "scoring_func"),
    ({"router_bias_update_rate": 0.0}, {}, "router_bias_update_rate"),
    ({"router_bias_update_rate": None}, {}, "router_bias_update_rate"),
], ids=["pipeline", "tensor", "context", "held-under-ep", "held-range", "query-latent",
        "yarn", "groups", "topk-method", "softmax", "bias-never-moves", "bias-rate-missing"])
def test_the_config_refuses_by_the_keys_name(model, ds, named):
    with pytest.raises(ValueError, match=named):
        kanana.KananaConfig.from_config({**MODEL, **model}, ds)


def test_the_flops_count_is_of_the_latents_projections_and_the_held_slots():
    cfg = config()
    bd = kanana.flops_breakdown(cfg, 4096)
    h, H = 64, 4
    projections = h * H * 24 + h * (24 + 8) + 24 * H * (16 + 16) + H * 16 * h
    scores = H * (24 + 16) * 4097 / 2
    assert bd["attention"] == pytest.approx(4 * 2 * (projections + scores), rel=1e-12)
    # 3 slots a token x 4 of 16 held = 0.75 expected slots, + two shared experts
    assert bd["mlp"] == pytest.approx(6 * h * 128 + 3 * 6 * h * 32 * (2 + 0.75), rel=1e-12)
    assert bd["router"] == 3 * 2 * h * 16 and bd["head"] == 2 * h * 256
    assert kanana.flops_breakdown(config(n_routed_experts=0, num_experts_held=None),
                                  4096)["router"] == 0


# -- through nxdt-train -----------------------------------------------------------


def test_the_example_config_trains_at_toy_counts_on_the_cpu_mesh(tmp_path, devices8):
    """``examples/conf/hf_kanana_2_30b_a3b_config.yaml`` at toy counts through
    ``Trainer.from_config(cfg).fit()`` on ep 4 x dp 2: every expert resident
    somewhere, the rows exchanged between the chips that hold them, the bias
    moving by the loads summed over the chips."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    toy = {f"model.{k}": v for k, v in MODEL.items()
           if k not in ("architecture", "num_experts_held")}
    cfg = load_config(str(ROOT / "examples/conf/hf_kanana_2_30b_a3b_config.yaml"), {
        **toy, "model.fusions.flash_attention": False,
        "distributed_strategy.expert_model_parallel_size": 4,
        "data.synthetic": True, "data.seq_length": SEQ, "data.global_batch_size": 8,
        "data.micro_batch_size": 1,
        "trainer.max_steps": 3, "trainer.log_every_n_steps": 1,
        "exp_manager.exp_dir": str(tmp_path), "exp_manager.resume_if_exists": False,
        "exp_manager.checkpoint_callback_params": None,
        "debug": {"validate_sharding": True}})
    trainer = Trainer.from_config(cfg, devices=devices8, enable_checkpointing=False)
    trainer.fit()
    log_dir = Path(trainer.exp.log_dir)
    rows = [json.loads(line) for line in open(log_dir / "metrics.jsonl")]
    assert [r["step"] for r in rows] == [1, 2, 3]
    assert all(np.isfinite(r["loss"]) and r["moe/recv_rows_share_max"] >= 1.0 for r in rows)
    assert [r["moe/bias_abs_max"] for r in rows] == pytest.approx([0.0, 0.001, 0.002])
    summary = json.load(open(log_dir / "run_summary.json"))
    assert summary["model_family"] == "KananaConfig" and summary["attention_kind"] == "mla"
    assert summary["moe_token_shards"] == 8 and "moe_experts_held" not in summary
    steps = np.asarray(at(trainer.params, BIAS), np.float64) / 0.001
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
