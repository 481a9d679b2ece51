"""A stack of single-mixer layers (``models/nemotron_h.py``): Mamba-2
state-space mixers, sigmoid-routed non-gated ``relu2`` experts beside a shared
expert, grouped-query attention with no position embedding, under an untied
head.  Held against the benchmark's plain reference
(``benchmark/references/nemotron_h.py``, float32, nothing of the program, the
scan as the recurrence itself) by the rungs of ``tests/family_ladder.py``, each
of four omissions and an omitted bias update shown to fail the parity the
first holds and the shares of all held ranges shown to add up to the whole;
``ops/ssd.py`` held against an explicit recurrence over positions at a length
that is no multiple of the chunk, with left padding and packed documents too;
the convolution with its bias and ``silu`` against a loop; the accepted
families' programs shown untouched."""

import dataclasses
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_ladder
from benchmark.reference import leaf_names
from family_ladder import FP32, worst_gap
from neuronx_distributed_training_tpu.models import nemotron_h as nh
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.models.laguna import stack_plan
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import short_conv as conv_ops
from neuronx_distributed_training_tpu.ops import ssd as ssd_ops
from neuronx_distributed_training_tpu.optim import adamw

#: the published shape at toy widths: every kind of layer, the two that carry
#: more than weights twice and as one period of the plan (``MEME*``), 4 Mamba
#: heads of 8 in 2 groups with a state of 16, 4 taps, chunks of 8, 4 query
#: heads on 1 key/value head of 16 dims, 16 experts of 24 of which a token
#: takes 3 and 4 are held, a shared expert of 48, an untied head
MODEL = dict(
    architecture="nemotron_h", vocab_size=256, hidden_size=64, num_hidden_layers=5,
    hybrid_override_pattern="MEME*", mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8, use_conv_bias=True,
    num_attention_heads=4, num_key_value_heads=1, head_dim=16, layer_norm_epsilon=1e-5,
    n_routed_experts=16, num_experts_per_tok=3, num_experts_held=[0, 4],
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, router_bias_update_rate=0.001,
    initializer_range=0.02, tie_word_embeddings=False,
    activations_checkpoint_granularity="full")
SEQ = 28   # no multiple of the chunk
MIXER, MOE = "layers/mamba/mixer/", "layers/moe/mlp/"
_H = 64
_MAMBA = 2 * _H * (32 + 96 + 4) + 2 * 32 * _H + 2 * 4 * 96 + 6 * 32 * 16
_ATTENTION = 2 * _H * (4 + 2) * 16 + 2 * 4 * 16 * _H + 4 * 4 * 16 * 4097 / 2

TOY = family_ladder.Toy(
    module=nh, config_class=nh.NemotronHConfig, reference="nemotron_h", model=MODEL, seq=SEQ,
    bias=(("layers", "moe", "mlp", "router", "bias"),),
    #: what this family brings (the taps, the route's bias and the renormalising
    #: are held by tests/test_lfm2.py and tests/test_kanana.py on the code they share)
    omissions=("conv_silu", "gate", "norm_groups", "skip"),
    leaf_tol=5e-5, moved=("norm", "head_scales", "conv/bias"), unscaled=("embed",),
    shapes={
        "lm_head/w": (64, 256),                                        # untied
        MIXER + "in_proj/w": (2, 64, 32 + (32 + 2 * 2 * 16) + 4),
        MIXER + "conv/w": (2, 4, 96), MIXER + "conv/bias": (2, 96),
        MIXER + "out_proj/w": (2, 32, 64),
        "layers/attention/attn/qkv/w": (1, 64, (4 + 2) * 16),
        MOE + "experts/gate_up": (2, 4, 64, 24),                       # 4 of 16 held; no gate
        MOE + "experts/down": (2, 4, 24, 64), MOE + "shared/gate_up/w": (2, 64, 48),
        MOE + "router/w": (2, 64, 16)},
    refusals={
        "pipeline": ({}, {"pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
        "tensor": ({}, {"tensor_model_parallel_size": 2}, "tensor_model_parallel_size"),
        "context": ({}, {"context_parallel_size": 2}, "context_parallel_size.*state S"),
        "sequence-parallel": ({}, {"sequence_parallel": True}, "sequence_parallel.*state S"),
        "held-under-ep": ({}, {"expert_model_parallel_size": 2}, "num_experts_held"),
        "held-range": ({"num_experts_held": [4, 20]}, {}, "num_experts_held"),
        "dense-mlp-layer": ({"hybrid_override_pattern": "M-M*E"}, {}, "'-'.*dense MLP"),
        "unknown-layer": ({"hybrid_override_pattern": "MXM*E"}, {}, "unknown"),
        "short-pattern": ({"hybrid_override_pattern": "MEM"}, {}, "hybrid_override_pattern has 3"),
        "n-group": ({"n_group": 2}, {}, "n_group"),
        "topk-group": ({"topk_group": 2}, {}, "topk_group"),
        "proj-bias": ({"mamba_proj_bias": True}, {}, "mamba_proj_bias"),
        "no-conv-bias": ({"use_conv_bias": False}, {}, "use_conv_bias"),
        "gated-experts": ({"mlp_hidden_act": "silu"}, {}, "mlp_hidden_act"),
        "bias-never-moves": ({"router_bias_update_rate": 0.0}, {}, "router_bias_update_rate")},
    # 3 slots a token x 4 of 16 held = 0.75 expected slots, beside the shared expert
    flops=(({}, {"attention": 2 * _MAMBA + _ATTENTION, "mlp": 2 * 4 * _H * (24 * 0.75 + 48),
                 "router": 2 * 2 * _H * 16, "head": 2 * _H * 256}),),
    shares=(("moe", 16),),
    summary={"model_family": "NemotronHConfig",
             "layer_kinds": {"mamba": 2, "moe": 2, "attention": 1},
             "ssd": {"heads": 4, "head_dim": 8, "state": 16, "groups": 2, "chunk": 8,
                     "way": ssd_ops.WAY, "bytes_per_token": 2 * (2 * 32 + 2 * 32 + 4)},
             "mamba_conv": {"taps": 4, "channels": 96, "way": conv_ops.CONV_WAY},
             "moe_expert_act": "relu2", "attention_positions": "none",
             "moe_experts_held": [0, 4, 16], "moe_score_func": "sigmoid",
             # _HELD_ROWS x the even share, 2 x 28 x 3 x 4 / 16 = 42 rows, in eights
             "moe_row_bounds": [8 * int(np.ceil(m * 42 / 8)) for m in moe_ops._HELD_ROWS]},
    example=("hf_nemotron3_nano_30b_a3b_config.yaml", (), {"data.micro_batch_size": 1},
             {"layer_kinds": {"mamba": 2, "moe": 2, "attention": 1}}))


@pytest.fixture(scope="module")
def programs():
    return family_ladder.Programs(TOY)


class TestLadder(family_ladder.BiasLadder):
    toy = TOY

    def test_the_seeded_scalars_of_a_head_and_every_stack_rematerialized_whole(
            self, programs, trained):
        scales = programs.weights(11, spread=False)["layers"]["mamba"]["mixer"]["head_scales"]
        np.testing.assert_allclose(np.asarray(scales["A_log"][0]), np.log(np.arange(1, 5)),
                                   rtol=1e-6)
        step = np.logaddexp(np.asarray(scales["dt_bias"], np.float64), 0.0)     # softplus
        assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
        remat = trained["summary"]["remat"]
        assert set(remat) == {"mamba", "moe", "attention"}
        assert all(entry["granularity"] == "full" for entry in remat.values())


config = TOY.config


def test_the_stack_plan_and_what_takes_no_weight_decay(programs):
    cfg = config(num_hidden_layers=9, hybrid_override_pattern="MEMEM*EMEMEM*")   # the benchmark's
    assert [k for (k,) in cfg.kinds] == ["mamba", "moe", "mamba", "moe", "mamba", "attention",
                                         "moe", "mamba", "moe"]
    plan = stack_plan(cfg.kinds)
    assert plan[0] == ("periods", 2, ((("mamba",), 1), (("moe",), 1)))
    assert [seg[:2] for seg in plan[1:]] == [("run", ("mamba",)), ("run", ("attention",)),
                                             ("run", ("moe",)), ("run", ("mamba",)),
                                             ("run", ("moe",))]
    full = nh.NemotronHConfig.from_config(
        {**MODEL, "num_hidden_layers": 52,
         "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}, {})
    assert (full.count("mamba"), full.count("moe"), full.count("attention")) == (23, 23, 6)
    assert [seg[0] for seg in stack_plan(config().kinds)] == ["periods", "run"]        # the toy's
    params = programs.weights(0, spread=False)
    mask = adamw.decay_mask(params, adamw.AdamWConfig())
    free = {n for n, m in zip(leaf_names(params), jax.tree_util.tree_leaves(mask)) if m == 0.0}
    assert free == {
        "final_norm/scale", "layers/attention/norm/scale", "layers/mamba/norm/scale",
        "layers/moe/norm/scale", "layers/mamba/mixer/gated_norm/scale",
        "layers/mamba/mixer/conv/bias", "layers/mamba/mixer/head_scales/A_log",
        "layers/mamba/mixer/head_scales/D", "layers/mamba/mixer/head_scales/dt_bias",
        "layers/moe/mlp/router/bias"}


# -- the scan -------------------------------------------------------------------


def loop_scan(x, B, C, dt, A_log, D, dt_bias, mask=None, segments=None):
    """The recurrence over positions, one token at a time."""
    b, s, h, p = x.shape
    r = h // B.shape[2]
    step = jax.nn.softplus(dt + dt_bias)
    if mask is not None:
        step, x = step * mask[..., None], x * mask[..., None, None]
    a = -jnp.exp(A_log)
    S = jnp.zeros((b, h, p, B.shape[3]))
    ys = []
    for t in range(s):
        decay = jnp.exp(step[:, t] * a)
        if segments is not None and t:
            decay = decay * (segments[:, t] == segments[:, t - 1])[:, None]
        Bt, Ct = jnp.repeat(B[:, t], r, axis=1), jnp.repeat(C[:, t], r, axis=1)
        S = (decay[..., None, None] * S
             + (step[:, t, :, None] * x[:, t])[..., None] * Bt[:, :, None, :])
        ys.append(jnp.einsum("bhpn,bhn->bhp", S, Ct) + D[:, None] * x[:, t])
    return jnp.stack(ys, axis=1)


def scan_operands(s=21, b=2, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return (jax.random.normal(k[0], (b, s, h, p)), jax.random.normal(k[1], (b, s, g, n)),
            jax.random.normal(k[2], (b, s, g, n)), jax.random.normal(k[3], (b, s, h)),
            jnp.log(jnp.arange(1, h + 1.0)), 1 + 0.1 * jax.random.normal(k[4], (h,)),
            0.1 * jax.random.normal(k[5], (h,)))


ROWS = {None: ({}, {}),
        "attention_mask": ("attention_mask", "mask", jnp.array([[0] * 5 + [1] * 16, [1] * 21])),
        "segment_ids": ("segment_ids", "segments",
                        jnp.array([[0] * 8 + [1] * 8 + [2] * 5, [0] * 3 + [1] * 18]))}


@pytest.mark.parametrize("rows", list(ROWS), ids=str)
def test_the_scan_and_every_gradient_are_the_recurrence_over_positions(rows):
    """21 tokens in chunks of 8 (no multiple: padded and cut off again), two
    groups of two heads; left padding (a padded position neither decays nor
    adds); packed documents that start at a chunk's start, inside one and
    twice in one (the state is reset there)."""
    operands = scan_operands()
    mine, theirs = {}, {}
    if rows is not None:
        name, ref_name, value = ROWS[rows]
        mine, theirs = {name: value}, {ref_name: value}
    def both(f):   # one compile a side: op by op the loop's 21 steps cost three times it
        out = jax.jit(jax.value_and_grad(lambda *a: (lambda y: (jnp.sum(jnp.sin(y)), y))(f(*a)),
                                         argnums=tuple(range(7)), has_aux=True))(*operands)
        return out[0][1], out[1]

    with jax.default_matmul_precision("highest"):
        y, got = both(lambda *a: ssd_ops.ssd_scan(*a, chunk=8, **mine))
        want, ref = both(lambda *a: loop_scan(*a, **theirs))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("x", "B", "C", "dt", "A_log", "D", "dt_bias"), got, ref):
        assert float(jnp.linalg.norm(a - b)) <= 2e-5 * float(jnp.linalg.norm(b)), name


def test_the_carried_state_is_float32(monkeypatch):
    """64 chunks of bf16-rounded state move the result past the tolerance the
    float32 state holds; and the scan over chunks is never one over tokens."""
    operands = scan_operands(s=512, b=1)
    operands = operands[:3] + (operands[3] - 3.0,) + operands[4:]      # slow decay: long memory
    want = loop_scan(*operands)

    def gap():
        with jax.default_matmul_precision("highest"):
            y = ssd_ops.ssd_scan(*operands, chunk=8)
        return float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))

    assert gap() < 1e-5
    monkeypatch.setattr(ssd_ops, "STATE_DTYPE", jnp.bfloat16)
    assert gap() > 3e-4
    monkeypatch.undo()
    text = str(jax.make_jaxpr(lambda *a: ssd_ops.ssd_scan(*a, chunk=8))(*operands))
    # 64 chunks: 16 blocks of 4, and the pass over a block's chunks; never the tokens
    assert "length=16" in text and "length=4" in text and "length=512" not in text


def test_a_changed_token_moves_nothing_before_it(programs):
    cfg, params = config(), programs.weights(1)
    toks = programs.tokens(2, rows=1)
    changed = toks.at[0, 13].set((toks[0, 13] + 1) % 256)
    logits = jax.jit(lambda t: nh.forward(params, {"input_ids": t}, cfg, FP32)[0])
    with jax.default_matmul_precision("highest"):
        a, b = np.asarray(logits(toks)), np.asarray(logits(changed))
    np.testing.assert_array_equal(a[0, :13], b[0, :13])
    assert np.all(np.any(a[0, 13:] != b[0, 13:], axis=-1))      # the state carries it to the end


def test_left_padding_and_packed_documents_reach_all_three_mixers(programs):
    """A left-padded row's real positions read what the row reads unpadded; a
    document packed behind another reads what it reads alone."""
    cfg, params = config(activations_checkpoint_granularity=None), programs.weights(1)
    toks = programs.tokens(3, rows=1, seq=24)
    rows = jnp.array([[0] * 8 + [1] * 16])

    @jax.jit
    def logits():
        run = lambda **batch: nh.forward(params, batch, cfg, FP32)[0]  # noqa: E731
        return (run(input_ids=toks[:, 8:]), run(input_ids=toks, attention_mask=rows),
                run(input_ids=toks, segment_ids=rows))

    with jax.default_matmul_precision("highest"):
        alone, padded, packed = map(np.asarray, logits())
    np.testing.assert_allclose(padded[0, 8:], alone[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(packed[0, 8:], alone[0], rtol=2e-4, atol=2e-5)


# -- the convolution and the gated norm ------------------------------------------


def test_the_convolution_with_its_bias_and_silu_is_the_loop_over_positions(rows="segment_ids"):
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (2, 21, 12))
    taps, bias = jax.random.normal(k[1], (4, 12)), jax.random.normal(k[2], (12,))
    kw = {} if rows is None else {ROWS[rows][0]: ROWS[rows][2]}
    want = np.zeros((2, 21, 12))
    xn = np.asarray(x)
    for b in range(2):
        for t in range(21):
            acc = np.asarray(bias).copy()
            for d in range(4):
                if t - d < 0:
                    continue
                if "attention_mask" in kw and not kw["attention_mask"][b, t - d]:
                    continue
                if "segment_ids" in kw and kw["segment_ids"][b, t - d] != kw["segment_ids"][b, t]:
                    continue
                acc += np.asarray(taps)[3 - d] * xn[b, t - d]
            want[b, t] = acc / (1 + np.exp(-acc))
    got = conv_ops.causal_conv(x, taps, bias, silu=True, **kw)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    plain = conv_ops.causal_conv(x, taps, None, **kw)
    assert float(jnp.max(jnp.abs(plain - got))) > 0.1
    with pytest.raises(ValueError, match="taps"):
        conv_ops.causal_conv(x, jnp.zeros((9, 12)))


def shifted_conv(x, taps, bias, mask=None, segments=None):
    """The convolution as shifted multiply-adds in plain ``jax.numpy``."""
    s, k = x.shape[1], taps.shape[0]
    acc = bias
    for d in range(k):
        back = jnp.pad(x, ((0, 0), (d, 0), (0, 0)))[:, :s]
        ok = jnp.ones(x.shape[:2], bool)
        if mask is not None:
            ok = ok & jnp.pad(mask.astype(bool), ((0, 0), (d, 0)))[:, :s]
        if segments is not None and d:
            ok = ok & (segments == jnp.pad(segments, ((0, 0), (d, 0)), constant_values=-1)[:, :s])
        acc = acc + taps[k - 1 - d] * jnp.where(ok[..., None], back, 0.0)
    return jax.nn.silu(acc)


@pytest.mark.parametrize("rows", list(ROWS), ids=str)
def test_the_convolutions_kernels_and_their_gradients_over_three_tiles(rows):
    """520 tokens: three tiles of 256 rows with the halo between them, the
    last one padded; value and the gradients of ``x``, taps and bias."""
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(k[0], (2, 520, 16))
    taps, bias = jax.random.normal(k[1], (4, 16)), jax.random.normal(k[2], (16,))
    mine, theirs = {}, {}
    if rows == "attention_mask":
        value = jnp.array([[0] * 300 + [1] * 220, [1] * 520])
        mine, theirs = {"attention_mask": value}, {"mask": value}
    if rows == "segment_ids":
        value = jnp.array([[0] * 255 + [1] * 3 + [2] * 262, [0] * 256 + [1] * 264])
        mine, theirs = {"segment_ids": value}, {"segments": value}
    got = jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(conv_ops.causal_conv(
        *a, silu=True, **mine))), argnums=(0, 1, 2))(x, taps, bias)
    want = jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(shifted_conv(*a, **theirs))),
                              argnums=(0, 1, 2))(x, taps, bias)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, a, b in zip(("x", "taps", "bias"), got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=name)


def test_the_gated_norm_gates_first_and_norms_inside_each_group():
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    y, z = jax.random.normal(k[0], (3, 32)), jax.random.normal(k[1], (3, 32))
    scale = 1 + 0.1 * jax.random.normal(k[2], (32,))
    got = np.asarray(norm_ops.apply_gated_rms_norm({"scale": scale}, y, z, groups=2, eps=1e-5))
    gated = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    want = np.concatenate([g / np.sqrt(np.mean(g * g, axis=-1, keepdims=True) + 1e-5)
                           for g in (gated[:, :16], gated[:, 16:])], axis=-1) * np.asarray(scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    one_group = np.asarray(norm_ops.apply_gated_rms_norm({"scale": scale}, y, z, groups=1, eps=1e-5))
    assert np.max(np.abs(one_group - got)) > 1e-2


# -- the experts ------------------------------------------------------------------


def test_relu2_experts_are_a_dense_sum_over_the_experts_beside_the_shared_expert(programs):
    """A sparse layer with all 16 non-gated experts in one program equals a
    dense sum over the experts, each weighed by the route's gate, plus the
    shared expert (the shares of the sixteen held ranges are the ladder's)."""
    uncut = {**MODEL, "num_experts_held": None}
    moe = nh.NemotronHConfig.from_config(uncut, {}).moe

    @jax.jit
    def both(params, key):
        layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"]["mlp"])
        assert layer["experts"]["gate_up"].shape == (16, 64, 24)
        z = jax.random.normal(key, (2, SEQ, 64), jnp.float32)
        whole, _ = moe_ops.moe_block(layer, z, moe, compute_dtype=jnp.float32)
        flat = z.reshape(-1, 64)
        probs, idx, _ = moe_ops.route(layer["router"], flat, moe)
        gates = jnp.zeros((flat.shape[0], 16)).at[jnp.arange(flat.shape[0])[:, None], idx].set(probs)
        every = jnp.einsum("tef,efh->teh", jnp.square(jax.nn.relu(
            jnp.einsum("th,ehf->tef", flat, layer["experts"]["gate_up"]))),
            layer["experts"]["down"])
        shared = (jnp.square(jax.nn.relu(flat @ layer["shared"]["gate_up"]["w"]))
                  @ layer["shared"]["down"]["w"])
        return whole.reshape(-1, 64), jnp.einsum("te,teh->th", gates, every) + shared

    with jax.default_matmul_precision("highest"):
        whole, dense = both(programs.weights(2, uncut), family_ladder.key_of(3))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense), rtol=1e-4, atol=1e-5)


# -- attention with no position embedding ---------------------------------------------


def test_flash_at_sixteen_query_heads_a_key_head_matches_core():
    """The published grouping, 16 : 1 at 128-dim heads, through the flash
    kernels (interpret mode) as they are, forward and gradients."""
    k = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(k[0], (1, 256, 16, 128))
    kk, v = jax.random.normal(k[1], (1, 256, 1, 128)), jax.random.normal(k[2], (1, 256, 1, 128))

    def run(impl):
        def f(q, kk, v):
            return jnp.sum(jnp.sin(attn_ops.attention(q, kk, v, impl=impl, causal=True,
                                                      softmax_dtype=jnp.float32)))
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f, argnums=(0, 1, 2))(q, kk, v)

    (flash, flash_grads), (core, core_grads) = run("flash"), run("core")
    assert float(flash) == pytest.approx(float(core), rel=1e-5)
    assert worst_gap(flash_grads, core_grads) < 1e-4


# -- the accepted families' programs are untouched --------------------------------

#: sha256 of the StableHLO text each accepted family's toy loss-and-gradient
#: lowered to on the parent commit (880d46b), read there: the roped attention
#: block, SwiGLU experts with a shared expert, the 3-tap
#: gated middle
ACCEPTED = {
    "llama": ({}, "feffd1d8f8f8209d3cf4b36c0c76843db670cc2f1ce8430471ed2b84202d33a6"),
    "kanana": ({"n_routed_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
                "n_shared_experts": 1, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
                "v_head_dim": 8, "kv_lora_rank": 16, "router_bias_update_rate": 0.001},
               "05884a57315d7d7e9ff326f90af4f0120941c5ff86344cf43f473e01536f7a73"),
    "lfm2": ({"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
              "num_dense_layers": 1, "layer_types": ["conv", "full_attention"],
              "router_bias_update_rate": 0.001}, "30f44bb2635bde6ec8252e3d2897d042a39c113ee8b7953bccf147f2f4d2d7e6"),
}


def accepted_text(arch, extra):
    family, cfg = resolve({"model": {
        "architecture": arch, "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_layers": 2, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "activations_checkpoint_granularity": None, **extra}})
    # shapes are all a lowering needs: nothing is drawn
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg, FP32))
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32), "labels": jnp.zeros((2, 16), jnp.int32)}
    return jax.jit(jax.grad(lambda p: family.loss(cfg, FP32)(p, batch, None)[0])).lower(
        params).as_text()


@pytest.mark.parametrize("arch", sorted(ACCEPTED))
def test_an_accepted_familys_program_is_the_program_it_was(arch):
    extra, sha = ACCEPTED[arch]
    text = accepted_text(arch, extra)
    assert hashlib.sha256(text.encode()).hexdigest() == sha
    for scope in ("mamba", "ssd_scan", "gated_norm"):
        assert scope not in text


def test_swiglu_is_the_default_and_relu2_another_program():
    cfg = moe_ops.MoEConfig(num_experts=4, top_k=2)
    assert cfg.expert_act == "swiglu"
    swiglu = moe_ops.init_moe_params(jax.random.PRNGKey(0), 16, 8, cfg)
    relu2 = moe_ops.init_moe_params(jax.random.PRNGKey(0), 16, 8,
                                    dataclasses.replace(cfg, expert_act="relu2"))
    assert swiglu["experts"]["gate_up"].shape == (4, 16, 16)
    assert relu2["experts"]["gate_up"].shape == (4, 16, 8)
    assert relu2["experts"]["down"].shape == swiglu["experts"]["down"].shape == (4, 8, 16)


def test_the_family_says_what_it_cannot():
    family, cfg = resolve({"model": MODEL})
    assert family is nh.FAMILY and cfg.family is family
    with pytest.raises(NotImplementedError, match="cached decode.*three kinds of state"):
        nh.FAMILY.decode()
    with pytest.raises(NotImplementedError, match="pipeline parallelism"):
        nh.FAMILY.pipeline(cfg, FP32)
    with pytest.raises(NotImplementedError, match="preference losses"):
        nh.FAMILY.head(cfg, FP32)
    from neuronx_distributed_training_tpu.tools import convert
    assert "nemotron" not in Path(convert.__file__).read_text()     # HF conversion: not wired
