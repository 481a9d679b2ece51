"""A decoder whose attention keys a learned indexer chooses, over
softmax-routed experts (``models/keye.py``, ``ops/sparse_attention.py``):
grouped-query attention with an RMS norm on every head's q and k, a second,
cheap attention that scores every causal pair, each query's ``topk``
best-scored keys kept, the softmax over those alone, and a KL loss that alone
trains the indexer; then experts chosen by softmax top-k, of which this
program may hold a range.  Held against the benchmark's plain reference
(``benchmark/references/keye.py``, float32, nothing of the program) by the
rungs of ``tests/family_ladder.py``, each of five omissions shown to fail the
parity the first holds; and what only this family has: no longer than ``topk``
the layer IS dense causal attention; past it every query keeps exactly
``min(topk, visible keys)``, none in the future, none outside its document,
none padded, the kernel's bisection and the reference's sort alike;
the indexer's leaves get their gradient from ``L_I`` alone and no other leaf
gets any from it; the eight held ranges' shares of a layer's experts, the
attention counted once, add up to the uncut reference's layer; the accepted
families' programs know nothing of it."""

import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_ladder
from benchmark.reference import leaf_names
from benchmark.references import keye as reference
from family_ladder import FP32, key_of
from neuronx_distributed_training_tpu.models import keye, llama
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.ops import attention as attention_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.ops import sparse_attention as sa_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd

#: the published shape at toy widths: 4 query / 2 key-value heads of 16 dims, 2
#: index heads of 8 over one index key a token, 8 keys a query of 32 in chunks
#: of 8 queries (so 3 of 4 chunks select), 16 experts of which a token takes 4
#: and 4 are held, an untied head
SA = dict(topk=8, indexer_num_heads=2, indexer_head_dim=8, indexer_num_kv_heads=1,
          q_chunk_size=8, kv_chunk_size=8)
MODEL = dict(
    architecture="keye", vocab_size=256, hidden_size=64, intermediate_size=192,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, rope_theta=1e7, initializer_range=0.02,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    num_experts=16, num_experts_held=[0, 4], num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, router_aux_loss_coef=0.001, tie_word_embeddings=False,
    sa_config=SA, activations_checkpoint_granularity="full")
SEQ = 32
_H, _HEADS, _GROUPS, _D, _HI, _DI = 64, 4, 2, 16, 2, 8
#: a token of 4096 keeps 8 keys but the first 8 queries, which keep t + 1
_KEPT = (8 * 9 / 2 + (4096 - 8) * 8) / 4096
_ATTENTION = (2 * _H * (_HEADS + 2 * _GROUPS) * _D + 2 * _HEADS * _D * _H
              + 4 * _HEADS * _D * _KEPT
              + 2 * _H * (_HI * _DI + _DI + _HI) * 2 / 3 + 2 * _HI * _DI * 4097 / 2)

TOY = family_ladder.Toy(
    module=keye, config_class=keye.KeyeConfig, reference="keye", model=MODEL, seq=SEQ,
    # (the reference knows more: ``rope``, ``index_norm``, ``renorm``, ``aux_loss``;
    # each costs a compile of it, and the accepted families' files hold their like)
    omissions=("selection", "indexer_loss", "qk_norm", "index_rope", "head_weights"),
    shapes={
        "layers/input_norm/scale": (2, 64), "layers/post_attn_norm/scale": (2, 64),
        "layers/attn/qkv/w": (2, 64, (4 + 2 * 2) * 16), "layers/attn/o/w": (2, 64, 64),
        "layers/attn/q_norm/scale": (2, 16), "layers/attn/k_norm/scale": (2, 16),
        "layers/attn/indexer/wq/w": (2, 64, 2 * 8), "layers/attn/indexer/wk/w": (2, 64, 8),
        "layers/attn/indexer/weights/w": (2, 64, 2),
        "layers/attn/indexer/k_norm/scale": (2, 8), "layers/attn/indexer/k_norm/bias": (2, 8),
        "layers/mlp/experts/down": (2, 4, 32, 64),                     # 4 of 16 held
        "layers/mlp/router/w": (2, 64, 16), "lm_head/w": (64, 256)},
    refusals={
        "pipeline": ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
        "tensor": ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
        "context": ({}, {"context_parallel_size": 2}, "context_parallel_size.*index keys"),
        "sequence-parallel": ({}, {"sequence_parallel": True}, "sequence_parallel.*index keys"),
        "held-under-ep": ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
        "held-range": ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
        "ring-kernels": ({"fusions": {"ring_attention": True}}, {}, "fusions.*mask by rule"),
        "window": ({"use_sliding_window": True}, {}, "use_sliding_window"),
        "tied-head": ({"tie_word_embeddings": True}, {}, "tie_word_embeddings"),
        "dense-layers": ({"mlp_only_layers": [0]}, {}, "mlp_only_layers"),
        "no-experts": ({"num_experts": 0, "num_experts_held": None}, {}, "num_experts"),
        "yarn": ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, {}, "rope_scaling"),
        "two-index-keys": ({"sa_config": {**SA, "indexer_num_kv_heads": 2}}, {},
                           "indexer_num_kv_heads"),
        "a-threshold-key": ({"sa_config": {**SA, "threshold": "top_k"}}, {},
                            "sa_config.*threshold"),
        "a-loss-coefficient-key": ({"sa_config": {**SA, "loss_coef": 0.5}}, {},
                                   "sa_config.*loss_coef"),
        "unknown-sa-key": ({"sa_config": {**SA, "window": 4}}, {}, "sa_config.*window")},
    # selected pairs alone for the main attention, every causal pair for the
    # index scores; 4 slots a token x 4 of 16 held = 1 expected slot a layer
    flops=(({}, {"attention": 2 * _ATTENTION, "mlp": 2 * 6 * _H * 32 * 1.0,
                 "router": 2 * 2 * _H * 16, "head": 2 * _H * 256}),
           ({"num_experts_held": None}, {"mlp": 2 * 6 * _H * 32 * 4.0})),
    shares=(),      # this file's own: the layers are one stack, the route counts no loads
    moved=("norm", "k_norm/bias"),
    summary={"model_family": "KeyeConfig",
             "sparse_attention": {"topk": 8, "index_heads": 2, "index_head_dim": 8,
                                  "way": "xla_chunks", "threshold": "pallas_bisect",
                                  "q_chunk": 8, "loss_passes_per_layer_application": 2},
             "moe_experts_held": [0, 4, 16], "moe_score_func": "softmax",
             # _HELD_ROWS x the even share, 2 x 32 x 4 x 4 / 16 = 64 rows
             "moe_row_bounds": [int(m * 64) for m in moe_ops._HELD_ROWS]},
    example=("hf_keye_vl2_30b_a3b_config.yaml", (), {"data.micro_batch_size": 1},
             {"sparse_attention": {"topk": 8, "index_heads": 2, "index_head_dim": 8,
                                   "way": "xla_chunks", "threshold": "pallas_bisect",
                                   "q_chunk": 8, "loss_passes_per_layer_application": 2}}))


@pytest.fixture(scope="module")
def programs():
    return family_ladder.Programs(TOY)


class TestLadder(family_ladder.Ladder):
    toy = TOY

    def test_the_three_losses_are_logged_apart_and_the_share_is_exact(self, trained):
        """``loss`` is their sum; a query of 32 keeps 8 keys but the first 8."""
        for r in trained["logged"]:
            assert r["loss"] == pytest.approx(
                r["lm_loss"] + r["router_aux_loss"] + r["dsa/indexer_loss"], rel=1e-6)
            assert r["dsa/indexer_loss"] == pytest.approx(2 * r["dsa/indexer_kl"], rel=1e-6)
            assert r["dsa/indexer_kl"] > 0
            assert r["dsa/kept_pairs_share"] == pytest.approx((36 + 24 * 8) / 528, rel=1e-6)
            assert 1.0 <= r["moe/load_max_share"] < 4.0
        remat = trained["summary"]["remat"]
        assert set(remat) == {"layers"} and remat["layers"]["granularity"] == "full"
        # the flash kernel's two outputs, as in every family, and ``L_I``'s gradient
        assert remat["layers"]["kept"] == ["flash_o", "flash_lse", *sa_ops.KEPT_NAMES]


config = TOY.config


# -- no longer than topk, the layer is dense causal attention -----------------------


def test_no_longer_than_topk_the_layer_is_dense_causal_attention():
    """With ``T <= topk`` nothing is selected: the op's output and its
    gradients are the program's dense causal attention's (``ops.attention.
    core_attention``, the selection off), every visible pair kept; past
    ``topk`` the two part."""
    b, t = 2, SEQ
    ks = jax.random.split(key_of(6), 7)
    shapes = ((b, t, _HEADS, _D), (b, t, _GROUPS, _D), (b, t, _GROUPS, _D), (b, t, _HI, _DI),
              (b, t, _DI), (b, t, _HI), (b, t, _HEADS, _D))
    *args, ct = (jax.random.normal(k, shape) for k, shape in zip(ks, shapes))

    def run(attend):
        def loss(q, k, v):
            out, stats = attend(q, k, v)
            return jnp.sum(out * ct), (out, stats)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(*args[:3])

    def sparse(topk):
        cfg = dataclasses.replace(config().sa, topk=topk)
        return run(lambda q, k, v: sa_ops.sparse_attention(q, k, v, *args[3:], cfg))

    (_, (dense, _)), dense_grads = run(
        lambda q, k, v: (attention_ops.core_attention(q, k, v, causal=True), None))
    (_, (wide, stats)), wide_grads = sparse(SEQ)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(dense), rtol=1e-5, atol=1e-6)
    for mine, theirs in zip(wide_grads, dense_grads):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), rtol=1e-5, atol=1e-5)
    assert float(stats["kept_pairs"]) == float(stats["causal_pairs"]) == b * 528
    (_, (narrow, stats)), _ = sparse(8)
    assert float(np.max(np.abs(np.asarray(narrow) - np.asarray(dense)))) > 1e-2
    assert float(stats["kept_pairs"]) == b * 228
    # the first ``topk`` queries see no more than ``topk`` keys: dense still
    np.testing.assert_allclose(np.asarray(narrow)[:, :8], np.asarray(dense)[:, :8],
                               rtol=1e-5, atol=1e-6)


# -- past topk every query keeps exactly min(topk, visible) keys --------------------


def _visible(t, segments=None, real=None):
    visible = np.tril(np.ones((t, t), bool))[None]
    if segments is not None:
        visible = visible & (segments[:, :, None] == segments[:, None, :])
    if real is not None:
        visible = visible & real[:, :, None] & real[:, None, :]
    return np.broadcast_to(visible, (2, t, t))


@pytest.mark.parametrize("rows", [None, "segment_ids", "attention_mask"], ids=str)
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "tied"])
def test_every_query_keeps_exactly_topk_visible_keys(rows, ties):
    """64 queries, 8 keys each: the count is ``min(8, visible keys)`` on every
    row, no key is in the future, outside the query's document or padded; the
    kernel's bisection and the reference's stable sort choose the same set,
    also where most scores tie (rounded to quarters, zeros of both signs among
    them: ties go to the lower index)."""
    t, topk = 64, 8
    scores = jax.random.normal(jax.random.PRNGKey(5), (2, t, t))
    if ties:
        scores = jnp.round(scores * 2) / 4 * jnp.where(scores > 1, -1.0, 1.0)
    segments = real = None
    if rows == "segment_ids":
        segments = np.stack([np.arange(t) // 20, np.arange(t) // 33])
    if rows == "attention_mask":
        real = np.arange(t)[None, :] >= np.array([[0], [23]])           # left padding
    visible = _visible(t, segments, real)
    sel = np.asarray(sa_ops.select(
        scores, jnp.asarray(visible), sa_ops.SparseAttentionConfig(topk=topk)))
    plain = np.stack([np.asarray(reference.chosen(scores[b], jnp.asarray(visible[b]), topk))
                      for b in range(2)])
    np.testing.assert_array_equal(sel, plain)
    assert not np.any(sel & ~visible)
    np.testing.assert_array_equal(sel.sum(-1), np.minimum(topk, visible.sum(-1)))
    if rows is None:
        assert plain[0, -1].sum() == topk and plain[0, 3].sum() == 4


@pytest.mark.parametrize("rows", ["segment_ids", "attention_mask"])
def test_packed_documents_and_left_padding_reach_the_selection(rows):
    """Through the whole op: the selected pairs are the sum over the rows of
    ``min(topk, visible)``; a token changed in one document moves no output of
    another; a padded query attends to nothing and its KL counts for nothing."""
    cfg = config().sa
    b, t = 2, SEQ

    def op(key, **kw):
        ks = jax.random.split(key, 6)
        q = jax.random.normal(ks[0], (b, t, 4, 16))
        k, v = (jax.random.normal(kk, (b, t, 2, 16)) for kk in ks[1:3])
        qi = jax.random.normal(ks[3], (b, t, 2, 8))
        return q, k, v, qi, jax.random.normal(ks[4], (b, t, 8)), jax.random.normal(ks[5], (b, t, 2))

    args = op(jax.random.PRNGKey(2))
    segments = real = None
    if rows == "segment_ids":
        segments = np.stack([np.arange(t) // 12, np.arange(t) // 20])
        kw = {"segment_ids": jnp.asarray(segments)}
    else:
        real = np.arange(t)[None, :] >= np.array([[0], [9]])
        kw = {"attention_mask": jnp.asarray(real.astype(np.int32))}
    out, stats = jax.jit(lambda *a: sa_ops.sparse_attention(*a, cfg, **kw))(*args)
    visible = _visible(t, segments, real)
    assert float(stats["kept_pairs"]) == np.minimum(cfg.topk, visible.sum(-1)).sum()
    assert float(stats["causal_pairs"]) == visible.sum()
    assert np.isfinite(float(stats["kl"])) and float(stats["kl"]) > 0
    # every key and value of row 0's first document (or row 1's padding) moved
    span = np.zeros((b, t, 1, 1), bool)
    if rows == "segment_ids":
        span[0, :12] = True
    else:
        span[1, :9] = True
    moved = list(args)
    for i in (1, 2):
        moved[i] = jnp.where(span, args[i] + 1.0, args[i])
    moved[4] = jnp.where(span[..., 0], args[4] + 1.0, args[4])
    out2, _ = jax.jit(lambda *a: sa_ops.sparse_attention(*a, cfg, **kw))(*moved)
    untouched = ~np.broadcast_to(span, out.shape)
    np.testing.assert_array_equal(np.asarray(out)[untouched], np.asarray(out2)[untouched])
    if rows == "attention_mask":
        assert not np.any(np.asarray(out)[1, :9])


# -- the indexer's leaves get their gradient from L_I alone -------------------------


def test_the_indexers_leaves_get_their_gradient_from_its_own_loss_alone(programs):
    """Two gradients: of cross entropy + the routers' loss, exactly zero on
    the indexer's five leaves and nowhere else; of ``L_I``, nonzero on those
    five and exactly zero on every other leaf."""
    params, toks = programs.weights(3), programs.tokens(1)
    cfg = config()

    def part(which):
        def loss(p):
            total, aux = keye.forward(p, {"input_ids": toks, "labels": toks}, cfg, FP32)
            return aux["dsa/indexer_loss"] if which == "indexer" else total - aux["dsa/indexer_loss"]
        return jax.jit(jax.grad(loss))(params)

    names = leaf_names(params)
    norms = {which: dict(zip(names, (float(jnp.max(jnp.abs(g))) for g in jax.tree_util.tree_leaves(
        part(which))))) for which in ("indexer", "rest")}
    indexer = [n for n in names if "/indexer/" in n]
    assert len(indexer) == 5
    for name in names:
        if name in indexer:
            assert norms["indexer"][name] > 0 and norms["rest"][name] == 0.0, name
        else:
            assert norms["indexer"][name] == 0.0 and norms["rest"][name] > 0, name


# -- the experts' shares add up ------------------------------------------------------


def test_the_shares_of_all_eight_held_ranges_make_the_layer(programs):
    """One layer's output with all 16 experts in one program equals the
    attention half counted once plus the sum over 8 chips of what each makes
    of the 2 experts it holds; and equals the uncut reference's layer.  A
    share alone is not the layer."""
    uncut = {**MODEL, "num_experts_held": None}
    cfg = keye.KeyeConfig.from_config(uncut, {})
    c = reference.dims(uncut)
    params = programs.weights(2, uncut)

    @jax.jit
    def run(params, key):
        lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = jax.random.normal(key, (2, SEQ, 64), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
        def rope_of(dim):
            return rope_ops.rope_cos_sin(positions, rope_ops.rope_frequencies(dim, theta=1e7),
                                         dtype=jnp.float32)

        def layer(held):
            held_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
            mine = lp if held is None else {**lp, "mlp": {**lp["mlp"], "experts": jax.tree_util.tree_map(
                lambda a: a[held[0]:held[1]], lp["mlp"]["experts"])}}
            return keye._decoder_layer(mine, x, rope_of(16), rope_of(8), held_cfg, FP32)[0]

        whole = layer(None)
        parts = [layer((lo, lo + 2)) for lo in range(0, 16, 2)]
        # a part is x + attention + its experts' share: the first half once
        half = layer_without_experts(lp, x, rope_of, cfg)
        total = half + sum(p - half for p in parts)
        plain = reference.layer_forward(lp, x.reshape(-1, 64), {**c, "lo": 0, "hi": 16},
                                        reference.plain._matmul(None), 2)[0]
        return whole, parts, total, plain.reshape(whole.shape), half

    def layer_without_experts(lp, x, rope_of, cfg):
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=1e-6)
        return x + keye._attention_block(lp["attn"], hidden, rope_of(16), rope_of(8), cfg, FP32)[0]

    with jax.default_matmul_precision("highest"):
        whole, parts, total, plain, half = jax.tree_util.tree_map(
            np.asarray, run(params, key_of(3)))
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(whole, plain, rtol=1e-4, atol=2e-5)
    assert np.linalg.norm(parts[0] - whole) > 0.5 * np.linalg.norm(whole - half)


# -- the accepted families' programs are untouched --------------------------------


@pytest.mark.parametrize("arch, extra", [
    ("llama", {}), ("mixtral", {"moe": {"num_experts": 4, "top_k": 2, "dropless": True}}),
    ("lfm2", {"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
              "layer_types": ["conv", "full_attention"], "num_dense_layers": 1,
              "router_bias_update_rate": 0.001}),
    ("kanana", {"n_routed_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
                "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
                "kv_lora_rank": 16, "router_bias_update_rate": 0.001})])
def test_an_accepted_familys_program_knows_nothing_of_the_selection(arch, extra, monkeypatch):
    """The indexer, the selection and its loss are this family's own block
    (``models/keye.py::_attention_block``) and op: ``models/llama.py``'s block,
    the flash kernels and ``ops/moe.py`` were not touched, and an accepted
    family's loss lowers with none of the scopes, no selection kernel and no
    indexer leaf.  What ``full`` keeps of its layers is what it kept before
    this family handed ``llama.checkpoint_layer`` names of its own: the
    gradient's text is the text under the policy as it was written then."""
    model = {"architecture": arch, "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
             "num_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, **extra}
    rows = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def lowered(granularity, grad=False, debug_info=True):
        family, cfg = resolve({"model": {
            **model, "activations_checkpoint_granularity": granularity}})
        params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg, FP32))
        assert not any("indexer" in n for n in leaf_names(params))
        loss = lambda p, b: family.loss(cfg, FP32)(p, b, None)[0]  # noqa: E731
        return jax.jit(jax.grad(loss) if grad else loss).lower(
            params, {"input_ids": rows, "labels": rows}).as_text(debug_info=debug_info)

    text = lowered(None)
    for name in ("indexer", "dsa_select", "indexer_loss", "attention/select", "dsa_loss"):
        assert name not in text, name
    # (without the locations, which name the lines of this file)
    now = lowered("full", grad=True, debug_info=False)
    assert "dsa_loss" not in now
    monkeypatch.setattr(llama, "_remat_policy", lambda granularity, kept=(): (
        jax.checkpoint_policies.save_only_these_names("flash_o", "flash_lse")
        if granularity == "full" else None))
    assert lowered("full", grad=True, debug_info=False) == now
    root = Path(keye.__file__).resolve().parents[1]
    for path in ("models/llama.py", "ops/flash_attention.py", "ops/attention.py", "ops/moe.py"):
        assert "sparse_attention" not in (root / path).read_text(), path


def test_the_band_helpers_the_masked_kernels_borrow_are_as_they_were_read():
    """``ops/sparse_attention.py``'s three masked kernels are a fork of the
    flash kernels' bodies over ``ops/flash_attention.py``'s private band
    helpers (``ROADMAP.md`` M8b deletes the fork when the accepted kernels
    take the mask as an operand).  Until then a refactor of that file has to
    fail HERE and not silently in this family: each name is there with the
    parameters the fork calls it by, and the kept outputs' names are two."""
    import inspect

    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    borrowed = {
        "_walk": ["span", "band", "outer", "j"],
        "_band": ["bq", "bkv", "num_q", "num_kv", "causal", "window", "q_offset"],
        "_kv_span": ["band", "qi"], "_q_span": ["band", "ki"],
        "_block_sizes": ["sq", "skv", "bq", "bkv", "dtype", "d"],
        "_tileable": ["sq", "skv", "d", "bq", "bkv"],
        "_delta_rows": ["g", "o", "dlse"]}
    for name, params in borrowed.items():
        found = list(inspect.signature(getattr(fa, name)).parameters)
        assert found[:len(params)] == params, name
    assert len(fa.KEPT_NAMES) == 2 and fa.SUBLANES == 8 and fa.LANES == 128
    # 4 query blocks of 8 against 2 key blocks of 16, causal: the last query
    # block walks both key blocks, the first key block all four query blocks
    band = fa._band(8, 16, 4, 2, True, None, 0)
    assert (band.kv, band.q) == (2, 4)
    assert fa._kv_span(band, 3) == (0, 1) and fa._q_span(band, 1) == (2, 3)
    used = set(re.findall(r"\bfa\.(\w+)", Path(sa_ops.__file__).read_text()))
    assert used == set(borrowed) | {"KEPT_NAMES", "SUBLANES", "LANES", "NEG_INF"}


def test_the_family_answers_to_both_names_and_says_what_it_cannot():
    for arch in ("keye", "KeyeVL2"):
        family, cfg = resolve({"model": {**MODEL, "architecture": arch}})
        assert family is keye.FAMILY and cfg.family is family
    with pytest.raises(NotImplementedError, match="cached decode.*index keys"):
        keye.FAMILY.decode()
    with pytest.raises(NotImplementedError, match="pipeline parallelism"):
        keye.FAMILY.pipeline(config(), FP32)
    with pytest.raises(NotImplementedError, match="manual-vjp"):
        keye.FAMILY.onef1b_head(config(), FP32)
    from neuronx_distributed_training_tpu.tools import convert
    assert "keye" not in Path(convert.__file__).read_text()      # HF conversion: not wired


# -- the masked flash kernels are the chunks of XLA ---------------------------------


@pytest.mark.parametrize("rows", [None, "packed-and-padded"], ids=str)
def test_the_masked_flash_kernels_agree_with_the_chunks_of_xla(rows):
    """At shapes that tile the kernels (256 tokens in chunks of 128, heads of
    64, 64 keys a query; interpret mode) the way the chip takes, the flash
    band walk with the selection as a mask operand and the heads' summed
    probabilities from its ``lse``, gives the numbers of the plain chunks: the
    output, ``L_I``, the selected pairs and all six gradients, also with
    packed documents and left padding."""
    b, t, nh, nkv, d, hi, di = 2, 256, 4, 2, 64, 2, 8
    cfg = sa_ops.SparseAttentionConfig(topk=64, index_heads=hi, index_head_dim=di, q_chunk=128)
    kw = {}
    if rows:
        kw = {"segment_ids": jnp.stack([jnp.arange(t) // 100, jnp.arange(t) // 77]),
              "attention_mask": (jnp.arange(t)[None, :] >= jnp.array([[0], [70]])).astype(jnp.int32)}
    assert sa_ops.way_for(cfg, t, d, nh, nkv, jnp.float32) == "flash_mask"
    assert sa_ops.way_for(cfg, 32, 16, nh, nkv, jnp.float32) == "xla_chunks"     # the toys
    # ``fusions.flash_attention`` asks for the kernels, as in every family
    assert config(fusions={"flash_attention": True}).sa.way == "flash_mask"
    assert config().sa.way == "xla_chunks"

    def run(way):
        way_cfg = dataclasses.replace(cfg, way=way)

        @jax.jit
        def both(key):
            ks = jax.random.split(key, 7)
            shapes = ((b, t, nh, d), (b, t, nkv, d), (b, t, nkv, d), (b, t, hi, di), (b, t, di),
                      (b, t, hi), (b, t, nh, d))
            *args, ct = (jax.random.normal(k, shape) for k, shape in zip(ks, shapes))

            def loss(*a):
                o, stats = sa_ops.sparse_attention(*a, way_cfg, **kw)
                return jnp.sum(o * ct) + 3.0 * stats["kl"], (o, stats)
            return jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)(*args)

        with jax.default_matmul_precision("highest"):
            return both(key_of(4))

    ((_, (out, stats)), grads), ((_, (plain, plain_stats)), plain_grads) = run("flash_mask"), run(
        "xla_chunks")
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain), rtol=2e-4, atol=2e-4)
    assert float(stats["kl"]) == pytest.approx(float(plain_stats["kl"]), rel=1e-5)
    assert float(stats["kept_pairs"]) == float(plain_stats["kept_pairs"])
    for mine, theirs in zip(grads, plain_grads):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), rtol=2e-3, atol=2e-3)


# -- L_I and its gradient are taken once, in the forward -----------------------------


def _loss_operands(rows):
    """What ``_indexer_loss`` takes at shapes that tile ``dsa_probs`` (256
    tokens in 2 chunks of 128, 64 keys a query, the second chunk selects):
    seeded ``qi``, ``ki``, ``wi``, ``q``, ``k``, the chunks' masks as the op
    makes them and the ``lse`` of the masked scores."""
    b, t, nh, nkv, d, hi, di, chunk = 2, 256, 4, 2, 64, 2, 8, 128
    cfg = sa_ops.SparseAttentionConfig(topk=64, index_heads=hi, index_head_dim=di, q_chunk=chunk)
    ks = jax.random.split(key_of(7), 5)
    shapes = ((b, t, hi, di), (b, t, di), (b, t, hi), (b, nh, t, d), (b, nkv, t, d))
    qi, ki, wi, q, k = (jax.random.normal(kk, shape) for kk, shape in zip(ks, shapes))
    segments = real = None
    if rows:
        segments = np.stack([np.arange(t) // 100, np.arange(t) // 77])
        real = np.arange(t)[None, :] >= np.array([[0], [70]])
    visible = _visible(t, segments, real)
    sels = []
    for r0 in range(0, t, chunk):
        shown = jnp.asarray(visible[:, r0:r0 + chunk, :r0 + chunk])
        scores = sa_ops.index_scores(qi[:, r0:r0 + chunk], ki[:, :r0 + chunk], wi[:, r0:r0 + chunk])
        sels.append((sa_ops.select(scores, shown, cfg) if r0 + chunk > cfg.topk else shown
                     ).astype(jnp.int8))
    keep = np.concatenate([np.pad(np.asarray(m), ((0, 0), (0, 0), (0, t - m.shape[2])))
                           for m in sels], axis=1) != 0
    logits = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, nh // nkv, axis=1)) / np.sqrt(d)
    lse = jax.nn.logsumexp(jnp.where(keep[:, None], logits, sa_ops.NEG_INF), axis=-1)
    lse = jnp.where(keep.any(-1)[:, None], lse, sa_ops.NEG_INF)
    real = jnp.ones((b, t), bool) if real is None else jnp.asarray(real)
    return (qi, ki, wi), (tuple(sels), q, k, lse, real), chunk


def _unfused_loss(qi, ki, wi, sels, q, k, lse, real, chunk):
    """``L_I`` as the parent composed it, for plain autodiff: a chunk's index
    scores, ``_chunk_probs``, ``_kl_rows``, the mean over the real queries."""
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (8,))
    kls = []
    for i, m in enumerate(sels):
        rows, s = slice(i * chunk, (i + 1) * chunk), (i + 1) * chunk
        p_sum = sa_ops._chunk_probs(m, q[:, :, rows], k[:, :, :s], lse[:, :, rows], True)
        scores = sa_ops.index_scores(qi[:, rows], ki[:, :s], wi[:, rows])
        kls.append(sa_ops._kl_rows(jnp.where(m != 0, p_sum / q.shape[1], 0.0), scores, m != 0))
    return jnp.sum(jnp.where(real, jnp.concatenate(kls, axis=1), 0.0)) / sa_ops._count(real)


@pytest.mark.parametrize("rows", [None, "packed-and-padded"], ids=str)
def test_the_loss_rule_gives_the_gradient_plain_autodiff_takes(rows):
    """``_indexer_loss`` takes ``L_I``'s gradient on ``qi``, ``ki``, ``wi`` in
    the forward and multiplies it by the cotangent in its backward rule
    (nothing comes through the scores' values it is handed):
    value and gradients are plain autodiff's of the unfused composition to
    float32 round-off, under a cotangent that is not 1, also with packed
    documents and left padding (a padded query counts for nothing)."""
    leaves, rest, chunk = _loss_operands(rows)

    def grads(loss):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda *a: 2.5 * loss(*a), argnums=(0, 1, 2)))(
                *leaves)

    def scores(qi, ki, wi):     # as the op hands them on from the selection
        return tuple(sa_ops.index_scores(qi[:, r0:r0 + chunk], ki[:, :r0 + chunk],
                                         wi[:, r0:r0 + chunk]) for r0 in range(0, qi.shape[1], chunk))

    mine, mine_grads = grads(
        lambda *a: sa_ops._indexer_loss(*a, scores(*a), *rest, jnp.float32, True))
    plain, plain_grads = grads(lambda *a: _unfused_loss(*a, *rest, chunk))
    assert float(mine) == pytest.approx(float(plain), rel=1e-6) and float(mine) > 0
    for got, want in zip(mine_grads, plain_grads):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                                   atol=1e-6 * scale)
    if rows:   # row 1's first 70 tokens are padding: no gradient reaches them
        assert not np.any(np.asarray(mine_grads[0])[1, :70])
        assert not np.any(np.asarray(mine_grads[1])[1, :70])


@pytest.mark.parametrize("named", [True, False], ids=["named", "name-left-out"])
def test_a_rematerialized_layer_forms_the_loss_once_because_the_gradient_is_named(named):
    """Under ``jax.checkpoint`` with the layer's policy (``llama._remat_policy``
    with this family's names, as ``models/keye.py`` asks for it) the gradient's
    jaxpr calls ``dsa_probs`` once a chunk: the gradient taken in the forward
    is kept as the rule's residual and the rerun forms nothing of the loss.  With the names left out of the
    policy the rerun has to build the residual again: twice a chunk."""
    b, t, nh, nkv, d, hi, di = 1, 256, 2, 1, 64, 2, 8
    cfg = sa_ops.SparseAttentionConfig(topk=64, index_heads=hi, index_head_dim=di, q_chunk=128)
    shapes = ((b, t, nh, d), (b, t, nkv, d), (b, t, nkv, d), (b, t, hi, di), (b, t, di),
              (b, t, hi))
    policy = llama._remat_policy("full", sa_ops.KEPT_NAMES if named else ())

    def layer(*a):
        o, stats = sa_ops.sparse_attention(*a, cfg)
        return jnp.sum(o) + stats["kl"]

    with shd.collect_trace_facts() as facts:
        text = str(jax.make_jaxpr(
            jax.grad(jax.checkpoint(layer, policy=policy), argnums=tuple(range(6))))(
            *(jax.ShapeDtypeStruct(shape, jnp.float32) for shape in shapes)))
    # the fact ``run_summary.json`` carries: this way goes through the rule
    assert facts["sparse_attention"]["way"] == "flash_mask"
    assert facts["sparse_attention"]["loss_passes_per_layer_application"] == 1
    chunks = t // cfg.q_chunk
    assert len(re.findall(r"name=dsa_probs\b", text)) == (chunks if named else 2 * chunks)
    assert len(re.findall(r"name=flash_sel_fwd\b", text)) == 1
