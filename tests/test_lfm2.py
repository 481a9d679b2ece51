"""A gated-short-convolution / attention stack with sigmoid-routed experts
(``models/lfm2.py``): layers whose operator (a depthwise causal convolution of
three taps between two input-dependent gates, or grouped-query attention with
an RMS norm on every head's q and k) and feed-forward (dense, or experts
chosen by ``sigmoid score + bias`` whose bias moves by the load after every
optimizer step, of which this program may hold a range) vary independently,
under a head tied to the embedding.  Held against the benchmark's plain
reference (``benchmark/references/lfm2.py``, float32, nothing of the program);
the convolution's middle held against an explicit loop over positions, with
left padding and packed documents too; each of six omissions and an omitted
bias update shown to fail the parity the first test holds; the flash kernels
at 64-dim heads held against core attention; the shares of all held ranges
shown to add up to the whole; the accepted families' programs shown
untouched."""

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
from neuronx_distributed_training_tpu.models import lfm2
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.models.laguna import stack_plan
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import short_conv as conv_ops
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ROOT = Path(__file__).resolve().parents[1]
#: the published shape at toy widths: the benchmark's depth 8 (c c a c c c a c,
#: two dense layers, six sparse), 4 query / 2 key-value heads of 16 dims, 3
#: taps, 16 experts of which a token takes 4 and 4 are held, a tied head
MODEL = dict(
    architecture="lfm2", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    layer_types=["conv", "conv", "full_attention", "conv"] * 2, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    initializer_range=0.02, num_dense_layers=2, num_experts=16, num_experts_held=[0, 4],
    num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True, router_bias_update_rate=0.001,
    activations_checkpoint_granularity="full")
OPTIM = {"lr": 1e-3, "weight_decay": 0.1, "betas": [0.9, 0.95], "eps": 1e-8,
         "sched": {"warmup_steps": 0, "max_steps": 100}}
FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
SEQ = 32
SPARSE = ("full_sparse", "conv_sparse")


def bias_path(stack):
    return ("layers", stack, "mlp", "router", "bias")


@pytest.fixture(scope="module")
def reference():
    return importlib.import_module("benchmark.references.lfm2")


def config(**over):
    return lfm2.Lfm2Config.from_config({**MODEL, **over}, {})


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
    rotation, a gate or the bias left out shows."""
    def leaf(path, x):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), sum(map(ord, name)))
        if "norm" in name:
            return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
        if name.endswith("router/bias"):
            return 0.1 * jax.random.normal(key, x.shape, x.dtype)
        return x * (1.0 if "embed" in name else 5.0)
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
    mine, theirs = lfm2.init_params(key, cfg, FP32), reference.init_params(MODEL, key)
    assert reference.leaf_names(mine) == reference.leaf_names(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    assert sorted(mine["layers"]) == ["conv_dense", "conv_sparse", "full_sparse"]
    assert "lm_head" not in mine and "final_norm" not in mine      # tied; embedding_norm
    conv = mine["layers"]["conv_sparse"]["conv"]
    assert conv["in_proj"]["w"].shape == (4, 64, 192) and conv["out_proj"]["w"].shape == (4, 64, 64)
    assert conv["taps"]["w"].shape == (4, 3, 64)
    attn = mine["layers"]["full_sparse"]["attn"]
    assert attn["qkv"]["w"].shape == (2, 64, (4 + 2 * 2) * 16) and attn["o"]["w"].shape == (2, 64, 64)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (2, 16)
    assert mine["layers"]["conv_dense"]["mlp"]["gate_up"]["w"].shape == (2, 64, 256)
    mlp = mine["layers"]["conv_sparse"]["mlp"]
    assert mlp["experts"]["down"].shape == (4, 4, 32, 64)           # 4 of 16 held
    assert mlp["router"]["w"].shape == (4, 64, 16) and mlp["router"]["bias"].shape == (4, 16)
    assert not np.any(np.asarray(mlp["router"]["bias"]))
    specs = lfm2.param_specs(cfg)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert jax.tree_util.tree_structure(specs, is_leaf=is_spec) == jax.tree_util.tree_structure(mine)
    for spec, leaf in zip(jax.tree_util.tree_leaves(specs, is_leaf=is_spec),
                          jax.tree_util.tree_leaves(mine)):
        assert len(spec) == leaf.ndim


def test_the_published_stack_is_a_run_of_two_nine_periods_and_two_runs():
    types = (["conv", "conv", "full_attention", "conv"] * 10)
    cfg = config(num_hidden_layers=40, layer_types=types)
    cd, fs, cs = ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse")
    assert stack_plan(cfg.kinds) == [("run", cd, 2), ("periods", 9, ((fs, 1), (cs, 3))),
                                     ("run", fs, 1), ("run", cs, 1)]
    # the benchmark's depth: five runs, no period
    assert stack_plan(config().kinds) == [("run", cd, 2), ("run", fs, 1), ("run", cs, 3),
                                          ("run", fs, 1), ("run", cs, 1)]


@pytest.mark.parametrize("granularity, depth", [(None, 8), ("selective", 8), ("full", 8),
                                                ("full", 14)],
                         ids=["none-8", "selective-8", "full-8", "full-14-periods"])
def test_loss_and_every_gradient_match_the_reference_in_float32(reference, granularity, depth):
    """Also at depth 14, where the stack plan holds a periodic segment: the
    loads come back in the order of each kind's stack."""
    model = {**MODEL, "num_hidden_layers": depth,
             "layer_types": (MODEL["layer_types"] * 2)[:depth],
             "activations_checkpoint_granularity": granularity}
    cfg = lfm2.Lfm2Config.from_config(model, {})
    if depth == 14:
        assert any(seg[0] == "periods" for seg in stack_plan(cfg.kinds))
    params = spread(lfm2.init_params(jax.random.PRNGKey(3), cfg, FP32))
    toks = tokens()
    loss, grads = value_and_grads(
        lambda p: lfm2.forward(p, batch_of(toks), cfg, FP32)[0], params)
    c = reference.dims(model)
    ref_loss, ref_grads = value_and_grads(
        lambda p: reference.microbatch_loss(p, toks, c)[0], params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for name, g, r in zip(reference.leaf_names(grads), jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(g - r)) <= 2e-5 * float(jnp.linalg.norm(r)), name
    # the bias steers and is never weighed: its gradient is exactly zero on both sides;
    # and the loads the rule reads are the reference's, layer for layer, expert for expert
    _, aux = lfm2.forward(params, batch_of(toks), cfg, FP32)
    ref_loads = reference.microbatch_loss(params, toks, c)[1]
    for stack in SPARSE:
        assert not np.any(np.asarray(at(grads, bias_path(stack))))
        assert not np.any(np.asarray(at(ref_grads, bias_path(stack))))
        np.testing.assert_array_equal(np.asarray(aux[f"{lfm2.COUNTS}/{stack}"]),
                                      np.asarray(ref_loads[stack]))
    counted = sum(float(jnp.sum(aux[f"{lfm2.COUNTS}/{stack}"])) for stack in SPARSE)
    assert counted == (depth - 2) * 2 * SEQ * 4                     # layers x tokens x k


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
        "exp_manager": {"exp_dir": str(tmp_path_factory.mktemp("lfm2")), "name": "lfm2"},
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
    selection biases of both sparse kinds among them: three steps of the rule
    on both sides."""
    trainer, logged, summary, ref, seed = trained
    assert [r["loss"] for r in logged] == pytest.approx(ref["loss"], rel=1e-5)
    dparam = checks.parameter_change_norms(reference, trainer.params, MODEL, seed)
    gaps = checks.leaf_gaps(dparam, ref["dparam"])
    assert max(gaps.values()) < 2e-4, max(gaps, key=gaps.get)
    for stack in SPARSE:
        assert dparam[f"layers/{stack}/mlp/router/bias"] > 0.001 * np.sqrt(2 * 16) * 0.5
    grad1 = checks.first_gradient_norms(reference, trainer.opt_state, 0.9)
    assert set(grad1) == set(ref["grad1"])
    for r in logged:
        assert r["moe/row_bound"] == 0.0 and r["moe/held_rows"] > 0
        assert 1.0 <= r["moe/load_max_share"] < 16 / 4 and "moe/held_rows_share" in r
        assert not any(k.startswith(lfm2.COUNTS) for k in r)
    assert [r["moe/bias_abs_max"] for r in logged] == pytest.approx([0.0, 0.001, 0.002])
    assert summary["model_family"] == "Lfm2Config"
    assert summary["layer_kinds"] == {"conv_dense": 2, "full_sparse": 2, "conv_sparse": 4}
    assert summary["operator_kinds"] == {"conv": 6, "full_attention": 2}
    assert summary["short_conv"] == {"taps": 3, "way": "pallas", "bytes_per_token": 512}
    assert summary["moe_experts_held"] == [0, 4, 16] and summary["moe_score_func"] == "sigmoid"
    # _HELD_ROWS x the even share, 2 x 32 x 4 x 4 / 16 = 64 rows
    assert summary["moe_row_bounds"] == [int(moe_ops._HELD_ROWS * 64)] == [192]
    assert set(summary["remat"]) == {"conv_dense", "full_sparse", "conv_sparse"}
    assert all(entry["granularity"] == "full" for entry in summary["remat"].values())


def test_the_bias_moves_by_the_rule_and_by_nothing_of_adamws(trained):
    """After three steps every element of each kind's bias is a whole number
    of steps of 0.001 (no decay, no moment's step mixed in) and the
    optimizer's moments for it are exactly zero."""
    trainer, *_ = trained
    for stack in SPARSE:
        steps = np.asarray(at(trainer.params, bias_path(stack)), np.float64) / 0.001
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
        assert set(np.round(steps).astype(int).ravel()) <= {-3, -2, -1, 0, 1, 2, 3}
        assert np.any(np.round(steps) != 0)
        for moment in ("mu", "nu"):
            assert not np.any(np.asarray(at(trainer.opt_state[moment], bias_path(stack))))


# -- the comparison is tight enough: what is left out shows ---------------------

OMISSIONS = ["in_gate", "out_gate", "taps", "qk_norm", "rope", "bias", "renorm"]


@pytest.fixture(scope="module")
def parity(reference):
    """The program's float32 loss and gradients on spread-out weights, and a
    comparison of them with the reference's with something left out."""
    cfg = config()
    params = spread(lfm2.init_params(jax.random.PRNGKey(7), cfg, FP32))
    toks = tokens(seed=4)
    loss, grads = value_and_grads(
        lambda p: lfm2.forward(p, batch_of(toks), cfg, FP32)[0], params)
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
    """Each part of a layer that the configuration states, left out of the
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
    names = [f"layers/{stack}/mlp/router/bias" for stack in SPARSE]
    gaps = checks.leaf_gaps(without["dparam"], with_rule["dparam"])
    for name in names:
        assert without["dparam"][name] == 0.0 < with_rule["dparam"][name]
        assert with_rule["grad1"][name] == 0.0
        assert gaps[name] == pytest.approx(1.0)
    assert max(v for k, v in gaps.items() if k not in names) < 0.1


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


def test_a_changed_token_moves_nothing_before_it_and_two_convolution_outputs_after():
    """Causality and the halo: through ONE convolution layer's operator a
    token's change reaches its own position and the 2 after it, nothing else;
    through the whole stack (attention among its layers) nothing before it."""
    cfg = config()
    params = spread(lfm2.init_params(jax.random.PRNGKey(1), cfg, FP32))
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["conv_sparse"]["conv"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 64), jnp.float32)
    t = 11
    moved = x.at[0, t].add(1.0)
    with jax.default_matmul_precision("highest"):
        d = np.abs(np.asarray(lfm2._conv_block(lp, moved, cfg) - lfm2._conv_block(lp, x, cfg)))[0]
    changed = np.flatnonzero(d.max(axis=-1) > 1e-6)
    assert list(changed) == [t, t + 1, t + 2]
    toks = tokens(seed=8, rows=1)
    other = toks.at[0, t].set((toks[0, t] + 1) % 256)
    with jax.default_matmul_precision("highest"):
        a, _ = lfm2.forward(params, {"input_ids": toks}, cfg, FP32)
        b, _ = lfm2.forward(params, {"input_ids": other}, cfg, FP32)
    gap = np.abs(np.asarray(a - b))[0].max(axis=-1)
    assert not np.any(gap[:t]) and gap[t] > 0


def test_left_padding_and_packed_documents_reach_both_operators():
    """A left-padded row's real tokens see what the unpadded row's see (the
    convolution's zeros before the first real token, attention's masked keys,
    positions from the first real token); a packed row's second document
    reads as it does alone."""
    cfg = config(activations_checkpoint_granularity=None)
    params = spread(lfm2.init_params(jax.random.PRNGKey(1), cfg, FP32))
    toks = tokens(seed=6, rows=1, seq=20)
    pad = 12
    padded = jnp.concatenate([jnp.zeros((1, pad), toks.dtype), toks], axis=1)
    mask = (jnp.arange(pad + 20) >= pad)[None].astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        alone, _ = lfm2.forward(params, {"input_ids": toks}, cfg, FP32)
        behind, _ = lfm2.forward(params, {"input_ids": padded, "attention_mask": mask}, cfg, FP32)
    np.testing.assert_allclose(np.asarray(behind[:, pad:]), np.asarray(alone), rtol=2e-4, atol=2e-4)
    first = tokens(seed=7, rows=1, seq=12)
    packed = jnp.concatenate([first, toks], axis=1)
    segments = jnp.concatenate([jnp.zeros((1, 12), jnp.int32), jnp.ones((1, 20), jnp.int32)], axis=1)
    with jax.default_matmul_precision("highest"):
        both, _ = lfm2.forward(params, {"input_ids": packed, "segment_ids": segments}, cfg, FP32)
    np.testing.assert_allclose(np.asarray(both[:, 12:]), np.asarray(alone), rtol=2e-4, atol=2e-4)


# -- the head norms ---------------------------------------------------------------


def test_every_head_is_normed_before_the_rope_by_one_scale_a_side():
    """``llama._attention_block`` with ``q_norm`` / ``k_norm`` leaves against
    the same block without them fed a qkv whose heads were normed by hand."""
    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.ops import rope as rope_ops

    cfg = config(activations_checkpoint_granularity=None)
    lc = cfg.llama
    lp = jax.tree_util.tree_map(
        lambda a: a[0], spread(lfm2.init_params(jax.random.PRNGKey(4), cfg, FP32))
        ["layers"]["full_sparse"]["attn"])
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


# -- the experts' shares add up -------------------------------------------------


def test_the_shares_of_all_held_ranges_make_the_layer(reference):
    """A sparse layer's MLP output with all 16 experts in one program equals
    the sum over 8 chips of what each makes of the 2 experts it holds (no
    shared expert to count once); and both equal the uncut reference."""
    cfg = config(num_experts_held=None)
    layer = jax.tree_util.tree_map(
        lambda a: a[0], spread(lfm2.init_params(jax.random.PRNGKey(2), cfg, FP32))
        ["layers"]["conv_sparse"]["mlp"])
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
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)
    for _, aux in parts:    # every chip routes over all 16 and counts the same loads
        np.testing.assert_array_equal(np.asarray(aux["expert_counts"]),
                                      np.asarray(whole_aux["expert_counts"]))
    c = reference.dims(MODEL)
    with jax.default_matmul_precision("highest"):
        uncut, loads = reference.expert_block(layer, z.reshape(-1, 64), c,
                                              reference.plain._matmul(None), held=(0, 16))
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, 64), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(whole_aux["expert_counts"]))


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
    """The band walk's three kernels, interpret mode, against core attention:
    forward and all three gradients, causal, 4 query heads a key/value head,
    also under a padding mask and packed segments."""
    ks = jax.random.split(jax.random.PRNGKey(64), 4)
    b, s, nh, nkv, d = 2, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, s, nh, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
    ct = jax.random.normal(ks[3], (b, s, nh, d), jnp.float32)
    mask = jnp.arange(s)[None, :] < jnp.array([[s], [s - 70]])
    segments = jnp.stack([jnp.arange(s) // 100, jnp.arange(s) // 64])
    kw = {"attention_mask": mask} if rows == "attention_mask" else (
        {"segment_ids": segments} if rows == "segment_ids" else {})
    bias = None
    if rows == "attention_mask":
        bias = attn_ops.padding_mask_bias(mask)
    if rows == "segment_ids":
        bias = attn_ops.segment_mask_bias(segments)
    keep = mask[:, :, None, None] if rows == "attention_mask" else 1.0

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * ct * keep)

    assert fa.flash_tileable(s, s, d, nh, nkv, block_q=128, block_kv=128)
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=128, block_kv=128, interpret=True, **kw)
    core = lambda q, k, v: attn_ops.core_attention(q, k, v, causal=True, bias=bias)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        assert out.shape == (b, s, nh, d)
        np.testing.assert_allclose(np.asarray(out * keep), np.asarray(core(q, k, v) * keep),
                                   rtol=2e-4, atol=2e-4)
        for a, c in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                        jax.grad(loss(core), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-3)


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
    params = family.init_params(jax.random.PRNGKey(0), cfg, FP32)
    names = leaf_names(params)
    assert not any("q_norm" in n or "k_norm" in n or "conv" in n for n in names)
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32), "labels": jnp.zeros((2, 16), jnp.int32)}
    text = jax.jit(lambda p: family.loss(cfg, FP32)(p, batch, None)[0]).lower(params).as_text(
        debug_info=True)
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


# -- what is not wired is refused by name ---------------------------------------


@pytest.mark.parametrize("model, ds, named", [
    ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
    ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
    ({}, {"context_parallel_size": 2}, "context_parallel_size.*halo"),
    ({}, {"sequence_parallel": True}, "sequence_parallel.*halo"),
    ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
    ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
    ({"conv_bias": True}, {}, "conv_bias"),
    ({"use_expert_bias": False}, {}, "use_expert_bias"),
    ({"layer_types": ["conv", "mamba"] * 4}, {}, "layer_types"),
    ({"rope_parameters": {"rope_type": "yarn", "factor": 4}}, {}, "rope_type"),
    ({"router_bias_update_rate": 0.0}, {}, "router_bias_update_rate"),
    ({"router_bias_update_rate": None}, {}, "router_bias_update_rate"),
], ids=["pipeline", "tensor", "context", "sequence-parallel", "held-under-ep", "held-range",
        "conv-bias", "no-expert-bias", "unknown-operator", "yarn", "bias-never-moves",
        "bias-rate-missing"])
def test_the_config_refuses_by_the_keys_name(model, ds, named):
    with pytest.raises(ValueError, match=named):
        lfm2.Lfm2Config.from_config({**MODEL, **model}, ds)


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


def test_the_flops_count_is_of_the_operators_and_the_held_slots():
    cfg = config()
    bd = lfm2.flops_breakdown(cfg, 4096)
    h, H, G, d = 64, 4, 2, 16
    conv = 2 * h * 4 * h + 2 * 3 * h + 2 * h
    attention = 2 * h * (H + 2 * G) * d + 2 * H * d * h + 4 * H * d * 4097 / 2
    assert bd["attention"] == pytest.approx(6 * conv + 2 * attention, rel=1e-12)
    # 4 slots a token x 4 of 16 held = 1 expected slot in each of 6 sparse layers
    assert bd["mlp"] == pytest.approx(2 * 6 * h * 128 + 6 * 6 * h * 32 * 1.0, rel=1e-12)
    assert bd["router"] == 6 * 2 * h * 16 and bd["head"] == 2 * h * 256
    assert lfm2.flops_breakdown(config(num_experts=0, num_experts_held=None), 4096)["router"] == 0


# -- through nxdt-train -----------------------------------------------------------


def test_the_example_config_trains_at_toy_counts_on_the_cpu_mesh(tmp_path, devices8):
    """``examples/conf/hf_lfm2_24b_a2b_config.yaml`` at toy counts through
    ``Trainer.from_config(cfg).fit()`` on ep 4 x dp 2: every expert resident
    somewhere, the rows exchanged between the chips that hold them, the bias
    moving by the loads summed over the chips."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    toy = {f"model.{k}": v for k, v in MODEL.items()
           if k not in ("architecture", "num_experts_held")}
    cfg = load_config(str(ROOT / "examples/conf/hf_lfm2_24b_a2b_config.yaml"), {
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
    assert summary["model_family"] == "Lfm2Config"
    assert summary["operator_kinds"] == {"conv": 6, "full_attention": 2}
    assert summary["moe_token_shards"] == 8 and "moe_experts_held" not in summary
    for stack in SPARSE:
        steps = np.asarray(at(trainer.params, bias_path(stack)), np.float64) / 0.001
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
