"""The mixed stack (``models/laguna.py``): full and window attention layers
with their own head counts, rotary embeddings and a per-head gate, a dense
first MLP, then routed experts of which this program may hold a range, beside
a shared expert.  Held against the benchmark's plain reference
(``benchmark/references/laguna.py``, float32, nothing of the program); the
shares of the experts shown to add up to the whole; each of seven omissions
shown to fail the parity the first test holds; the flash kernels held against
core attention at the two GQA groups; the held rows' bound shown to change
nothing but the path."""

import dataclasses
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check as checks
from neuronx_distributed_training_tpu.models import laguna
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.ops.flash_attention import flash_attention
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ROOT = Path(__file__).resolve().parents[1]
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
OPTIM = {"lr": 1e-3, "weight_decay": 0.1, "betas": [0.9, 0.95], "eps": 1e-8,
         "sched": {"warmup_steps": 0, "max_steps": 100}}
FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
SEQ = 32


@pytest.fixture(scope="module")
def reference():
    return importlib.import_module("benchmark.references.laguna")


def config(**over):
    return laguna.LagunaConfig.from_config({**MODEL, **over}, {})


def tokens(seed=1, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, MODEL["vocab_size"])


def batch_of(toks):
    return {"input_ids": toks, "labels": toks}


def spread(params, seed=9):
    """Norm scales moved off their initial 1 and every other weight grown
    fivefold, so that attention is far from uniform and a gate, a window or a
    rotation left out shows."""
    def leaf(path, x):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if "norm" in name:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), sum(map(ord, name)))
            return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
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
    mine, theirs = laguna.init_params(key, cfg, FP32), reference.init_params(MODEL, key)
    assert reference.leaf_names(mine) == reference.leaf_names(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    assert sorted(mine["layers"]) == ["full_dense", "full_sparse", "sliding_sparse"]
    # the two kinds' projections differ in shape; the held experts are 4 of 16
    assert mine["layers"]["sliding_sparse"]["attn"]["qkv"]["w"].shape == (3, 64, (6 + 4) * 16)
    assert mine["layers"]["full_sparse"]["attn"]["qkv"]["w"].shape == (1, 64, (4 + 4) * 16)
    assert mine["layers"]["full_sparse"]["mlp"]["experts"]["down"].shape == (1, 4, 32, 64)
    assert mine["layers"]["full_sparse"]["mlp"]["router"]["w"].shape == (1, 64, 16)
    specs = laguna.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    ) == jax.tree_util.tree_structure(mine)
    for spec, leaf in zip(
            jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
            jax.tree_util.tree_leaves(mine)):
        assert len(spec) == leaf.ndim


@pytest.mark.parametrize("granularity", [None, "selective", "full"])
def test_loss_and_every_gradient_match_the_reference_in_float32(reference, granularity):
    cfg = config(activations_checkpoint_granularity=granularity)
    params = spread(laguna.init_params(jax.random.PRNGKey(3), cfg, FP32))
    toks = tokens()
    loss, grads = value_and_grads(
        lambda p: laguna.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(MODEL)
    ref_loss, ref_grads = value_and_grads(
        lambda p: reference.microbatch_loss(p, toks, c), params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for name, g, r in zip(reference.leaf_names(grads), jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(g - r)) <= 2e-5 * float(jnp.linalg.norm(r)), name


def test_three_adamw_steps_match_the_reference_in_float32(reference, tmp_path):
    """``Trainer.from_config(cfg).fit()`` in float32 against ``reference.run``:
    the losses of three steps and the parameters' change, leaf by leaf."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.data.loader import DataModule
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    seed, rows = 5, 2
    steps = [np.asarray(tokens(seed=100 + k, rows=rows)) for k in range(3)]

    class Rows(DataModule):
        def fetch_rows(self, idx):
            return {"input_ids": np.stack([steps[i // rows][i % rows] for i in idx])}

    cfg = load_config({
        "seed": seed, "model": {**MODEL, "optim": {"name": "adamw_fp32OptState", **OPTIM}},
        "distributed_strategy": {"tensor_model_parallel_size": 1},
        "data": {"global_batch_size": rows, "micro_batch_size": rows, "seq_length": SEQ},
        "trainer": {"max_steps": 3, "log_every_n_steps": 1, "gradient_clip_val": 1.0},
        "exp_manager": {"exp_dir": str(tmp_path), "name": "laguna"},
        "precision": {"type": "fp32"}})
    trainer = Trainer.from_config(cfg, data_module=Rows(1 << 10, rows),
                                  devices=jax.devices()[:1], enable_checkpointing=False)
    with jax.default_matmul_precision("highest"):
        trainer.fit()
    rows_logged = [json.loads(line) for line in
                   open(Path(trainer.exp.log_dir) / "metrics.jsonl")]
    ref = reference.run(MODEL, OPTIM, 1.0, [s[None] for s in steps], seed)
    assert [r["loss"] for r in rows_logged] == pytest.approx(ref["loss"], rel=1e-5)
    dparam = checks.parameter_change_norms(reference, trainer.params, MODEL, seed)
    assert max(checks.leaf_gaps(dparam, ref["dparam"]).values()) < 2e-4
    for r in rows_logged:
        assert r["moe/row_bound"] == 0.0 and r["moe/held_rows"] > 0
        assert r["moe/held_rows_share"] == pytest.approx(
            r["moe/held_rows"] / (rows * SEQ * 4 * 4 / 16), rel=1e-6)
        assert 0 < r["router_aux_loss"] < 0.01
    summary = json.load(open(Path(trainer.exp.log_dir) / "run_summary.json"))
    assert summary["layer_kinds"] == {
        "attention": {"full_attention": 2, "sliding_attention": 3},
        "mlp": {"dense": 1, "sparse": 4}}
    assert summary["moe_experts_held"] == [0, 4, 16]
    # _HELD_ROWS x the even share of 2 x 32 x 4 x 4 / 16 = 64 rows
    assert summary["moe_row_bounds"] == [int(moe_ops._HELD_ROWS * 64)] == [192]


# -- the comparison is tight enough: what is left out shows ---------------------

OMISSIONS = ["head_gate", "window", "partial_rotary", "attention_factor", "scale",
             "renorm", "shared"]


@pytest.fixture(scope="module")
def parity(reference):
    """The program's float32 loss and gradients on spread-out weights, and a
    comparison of them with the reference's with something left out."""
    cfg = config()
    params = spread(laguna.init_params(jax.random.PRNGKey(7), cfg, FP32))
    toks = tokens(seed=4)
    loss, grads = value_and_grads(
        lambda p: laguna.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(MODEL)

    def against(left_out=()):
        ref_loss, ref_grads = value_and_grads(
            lambda p: reference.microbatch_loss(p, toks, c, left_out=left_out), params)
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


# -- the experts' shares add up -------------------------------------------------


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_routed_parts_and_one_shared_expert_make_the_uncut_layer(reference, shares):
    """A sparse layer's MLP output with all 16 experts in one program equals
    the sum over ``shares`` chips of what each makes of the experts it holds,
    plus the shared expert counted once; and both equal the uncut reference."""
    cfg = config(num_experts_held=None)
    layer = jax.tree_util.tree_map(
        lambda a: a[0], spread(laguna.init_params(jax.random.PRNGKey(2), cfg, FP32))
        ["layers"]["full_sparse"]["mlp"])
    assert layer["experts"]["gate_up"].shape[0] == 16
    z = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)

    def block(params, held):
        moe = dataclasses.replace(cfg.moe, experts_held=held)
        with jax.default_matmul_precision("highest"):
            return moe_ops.moe_block(params, z, moe, compute_dtype=jnp.float32)[0]

    whole = block(layer, None)
    per = 16 // shares
    routed = sum(block({"router": layer["router"], "experts": jax.tree_util.tree_map(
        lambda a, s=s: a[s * per:(s + 1) * per], layer["experts"])},
        (s * per, (s + 1) * per)) for s in range(shares))
    shared = moe_ops._shared_expert(layer["shared"], z, jnp.float32)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    c = {**reference.dims(MODEL), "lo": 0, "hi": 16}
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.expert_block(layer, z.reshape(-1, 64), c,
                                          reference.plain._matmul(None))
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, 64), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    # a share alone is not the layer: most of a token's experts lie elsewhere
    assert float(jnp.linalg.norm(block(
        {**layer, "experts": jax.tree_util.tree_map(lambda a: a[:per], layer["experts"])},
        (0, per)) - whole)) > 0.1 * float(jnp.linalg.norm(whole))


# -- the held rows' bound -------------------------------------------------------


@pytest.mark.parametrize("bound", [0.25, 0.6], ids=["many-slices", "two-slices"])
def test_a_step_past_the_bound_gives_the_same_loss_and_gradients(monkeypatch, bound):
    """Forced past the bound (a bound under the rows held), the block runs
    slices of the sorted rows; the loss and every gradient are those of the
    one pass under the bound, and the counter says which way a step went."""
    cfg = config()
    params = spread(laguna.init_params(jax.random.PRNGKey(5), cfg, FP32))
    batch = batch_of(tokens(seed=6))

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p: laguna.forward(p, batch, cfg, FP32), has_aux=True))(params)

    (under, under_aux), under_grads = run()
    monkeypatch.setattr(moe_ops, "_HELD_ROWS", bound)
    (past, past_aux), past_grads = run()
    assert float(under_aux["moe/row_bound"]) == 0.0 and float(past_aux["moe/row_bound"]) == 1.0
    assert float(past_aux["moe/held_rows"]) == float(under_aux["moe/held_rows"])
    assert float(past_aux["moe/held_rows_share"]) > bound
    assert float(past) == pytest.approx(float(under), rel=1e-6)
    assert worst_gap(past_grads, under_grads) < 2e-5


@pytest.mark.parametrize("top_k", [4, 6, 10])
@pytest.mark.parametrize("side", [-1, 1], ids=["just-under", "just-over"])
def test_the_bound_changes_the_path_and_not_the_result(monkeypatch, top_k, side):
    """Rows held one short of the bound (one pass) and one past it (two
    slices): the block's output and every gradient are those of a bound of
    4 x the even share, which takes both in one pass."""
    tokens, experts_n, held, h, f = 32, 32, 8, 16, 24
    rng = np.random.default_rng(top_k)
    # a quarter of the experts held and three quarters of the choices among
    # them, spread evenly over the tokens: the bound exactly; then one more or less
    rows = int(moe_ops._HELD_ROWS * tokens * top_k * held / experts_n)
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
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(
                experts, x, probs)

    (ours, stats), grads = run()
    assert float(stats["moe/held_rows"]) == rows + side
    assert float(stats["moe/row_bound"]) == (side > 0)
    monkeypatch.setattr(moe_ops, "_HELD_ROWS", 4.0)
    (wide, wide_stats), wide_grads = run()
    assert float(wide_stats["moe/row_bound"]) == 0.0
    assert float(ours) == pytest.approx(float(wide), rel=1e-6)
    assert worst_gap(grads, wide_grads) < 2e-5


@pytest.mark.parametrize("held, held_tokens, bound, by_slices", [
    (4, 64, 192, True),     # every row held, 4 x the even share: by slices
    (4, 48, 192, False),    # the bound exactly: one pass
    (12, 64, 256, False),   # 3 x the even share of 192 is over the 256 rows
], ids=["all-rows-held", "at-the-bound", "never-over-the-rows"])
def test_the_bound_is_a_multiple_of_the_even_share_and_never_over_the_rows(
        held, held_tokens, bound, by_slices):
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    z = jnp.zeros((64, 8))
    # a token's four choices: the first four held experts, or four held by none
    idx = jnp.where(jnp.arange(64)[:, None] < held_tokens, jnp.arange(4)[None], 12 + jnp.arange(4))
    probs = jnp.full((64, 4), 0.25)
    experts = {"gate_up": jnp.zeros((held, 8, 16)), "down": jnp.zeros((held, 8, 8))}
    cfg = moe_ops.MoEConfig(num_experts=16, top_k=4, experts_held=(0, held))
    with shd.collect_trace_facts() as traced:
        _, stats = moe_ops._dropless_held(experts, z, probs, idx, cfg,
                                          compute_dtype=jnp.float32)
    even = 64 * 4 * held / 16
    assert traced["moe_row_bounds"] == [bound] == [min(int(moe_ops._HELD_ROWS * even), 256)]
    assert float(stats["moe/held_rows"]) == 4 * held_tokens
    assert float(stats["moe/held_rows_share"]) == pytest.approx(4 * held_tokens / even)
    assert float(stats["moe/row_bound"]) == by_slices
    with pytest.raises(NotImplementedError, match="experts_held"):
        moe_ops._dropless_experts(experts, z, probs, idx, cfg, compute_dtype=jnp.float32,
                                  expert_axis="expert")


# -- the kernels at this model's shapes -----------------------------------------


@pytest.mark.parametrize("nh, window", [(12, 64), (18, 64), (12, None)],
                         ids=["group-6-window", "group-9-window", "group-6-causal"])
def test_flash_matches_core_at_gqa_groups_of_6_and_9(nh, window):
    """The flash kernels, interpret mode, against core attention: 2 kv heads
    shared by groups of 6 and 9 query heads, a window of 64 under key tiles of
    128 (narrower than the tile, as 512 is under the default 2048)."""
    ks = jax.random.split(jax.random.PRNGKey(nh), 4)
    q = jax.random.normal(ks[0], (1, 256, nh, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 128), jnp.float32)
    ct = jax.random.normal(ks[3], (1, 256, nh, 128), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * ct)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, sliding_window=window, block_q=128, block_kv=128, interpret=True)
    core = lambda q, k, v: attn_ops.core_attention(  # noqa: E731
        q, k, v, causal=True, sliding_window=window)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(core(q, k, v)),
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                        jax.grad(loss(core), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


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


def test_a_stack_of_two_periods_runs_as_the_layers_one_by_one(reference):
    """1 + 8 layers (a prefix and two periods): the scan over periods takes
    each kind's layers from its stack in layer order."""
    deep = {**MODEL, "num_hidden_layers": 9,
            "layer_types": MODEL["layer_types"] + ["sliding_attention"] * 3 + ["full_attention"],
            "mlp_layer_types": MODEL["mlp_layer_types"] + ["sparse"] * 4}
    cfg = laguna.LagunaConfig.from_config(deep, {})
    assert [seg[0] for seg in laguna.stack_plan(cfg.kinds)] == ["run", "periods"]
    params = spread(laguna.init_params(jax.random.PRNGKey(8), cfg, FP32))
    toks = tokens(seed=3)
    loss, grads = value_and_grads(
        lambda p: laguna.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(deep)
    ref_loss, ref_grads = value_and_grads(
        lambda p: reference.microbatch_loss(p, toks, c), params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    assert worst_gap(grads, ref_grads) < 5e-5


# -- what is not wired is refused by name ---------------------------------------


@pytest.mark.parametrize("model, ds, named", [
    ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
    ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
    ({}, {"context_parallel_size": 2}, "context_parallel_size"),
    ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
    ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 5, 4]}, {}, "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": {"sliding_attention": 5}}, {}, "num_key_value_heads"),
    ({"layer_types": ["full_attention"] * 3}, {}, "layer_types"),
    ({"mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "moe"]}, {}, "mlp_layer_types"),
    ({"sliding_window": None}, {}, "sliding_window"),
    ({"gating": "per-element"}, {}, "gating"),
    ({"rope_parameters": {**ROPE, "full_attention": {"rope_type": "llama3"}}}, {}, "rope_type"),
], ids=["pipeline", "tensor", "context", "held-under-ep", "held-range", "heads-differ-in-a-kind",
        "heads-over-kv", "short-list", "unknown-mlp", "no-window", "gating", "rope-type"])
def test_the_config_refuses_by_the_keys_name(model, ds, named):
    with pytest.raises(ValueError, match=named):
        laguna.LagunaConfig.from_config({**MODEL, **model}, ds)


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


def test_the_flops_count_caps_the_window_and_counts_the_held_slots():
    cfg = config()
    bd = laguna.flops_breakdown(cfg, 4096)
    h, d = 64, 16
    full = 2 * h * (4 + 4) * d + 2 * 4 * d * h + 2 * h * 4 + 4 * 4 * d * (4097 / 2)
    keys = (8 * 9 / 2 + (4096 - 8) * 8) / 4096
    sliding = 2 * h * (6 + 4) * d + 2 * 6 * d * h + 2 * h * 6 + 4 * 6 * d * keys
    assert bd["attention"] == pytest.approx(2 * full + 3 * sliding, rel=1e-12)
    # 4 slots a token x 4 of 16 held = 1 expected slot, + the shared expert
    assert bd["mlp"] == pytest.approx(6 * h * 128 + 4 * 6 * h * (32 + 1 * 32), rel=1e-12)
    assert bd["router"] == 4 * 2 * h * 16 and bd["head"] == 2 * h * 256
    uncut = laguna.flops_breakdown(config(num_experts_held=None), 4096)
    assert uncut["mlp"] == pytest.approx(6 * h * 128 + 4 * 6 * h * (32 + 4 * 32), rel=1e-12)


# -- through nxdt-train -----------------------------------------------------------


def test_the_example_config_trains_at_toy_counts_on_the_cpu_mesh(tmp_path, devices8):
    """``examples/conf/hf_laguna_s_2_1_config.yaml`` at toy counts through
    ``Trainer.from_config(cfg).fit()`` on ep 4 x dp 2: every expert resident
    somewhere, the rows exchanged between the chips that hold them."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    toy = {f"model.{k}": v for k, v in MODEL.items()
           if k not in ("architecture", "num_experts_held", "layer_types", "mlp_layer_types",
                        "num_attention_heads_per_layer", "rope_parameters")}
    cfg = load_config(str(ROOT / "examples/conf/hf_laguna_s_2_1_config.yaml"), {
        **toy, "model.num_hidden_layers": 9, "model.fusions.flash_attention": False,
        "model.num_attention_heads_per_layer": [4, 6, 6, 6] * 12,
        "distributed_strategy.expert_model_parallel_size": 4,
        "data.synthetic": True, "data.seq_length": SEQ, "data.global_batch_size": 8,
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
    summary = json.load(open(log_dir / "run_summary.json"))
    assert summary["model_family"] == "LagunaConfig"
    assert summary["layer_kinds"]["attention"] == {"full_attention": 3, "sliding_attention": 6}
    assert summary["moe_token_shards"] == 8 and "moe_experts_held" not in summary
