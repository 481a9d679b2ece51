"""Numerics gate for the Pallas flash-attention kernel: forward and gradients
must match core_attention (the reference-numerics implementation) in
interpreter mode on CPU (SURVEY.md §4 plan item (a))."""

import itertools

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from neuronx_distributed_training_tpu.ops.attention import core_attention
from neuronx_distributed_training_tpu.ops.flash_attention import flash_attention


def _make_qkv(key, b, sq, skv, nh, nkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, nh, d), dtype)
    k = jax.random.normal(kk, (b, skv, nkv, d), dtype)
    v = jax.random.normal(kv, (b, skv, nkv, d), dtype)
    return q, k, v


CASES = [
    # (sq, skv, nh, nkv, window, causal)
    (256, 256, 2, 2, None, True),     # MHA causal
    (256, 256, 4, 2, None, True),     # GQA
    (256, 512, 2, 1, None, False),    # cross-length, non-causal, MQA
    (256, 256, 2, 2, 128, True),      # sliding window
]


@pytest.mark.parametrize("sq,skv,nh,nkv,window,causal", CASES)
def test_flash_matches_core_fwd_and_grad(sq, skv, nh, nkv, window, causal):
    q, k, v = _make_qkv(jax.random.PRNGKey(0), 2, sq, skv, nh, nkv, 128)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, sliding_window=window,
            block_q=128, block_kv=128, interpret=True,
        )
        return jnp.sum(o * o)

    def loss_core(q, k, v):
        o = core_attention(q, k, v, causal=causal, sliding_window=window)
        return jnp.sum(o * o)

    (lf, gf) = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    (lc, gc) = jax.value_and_grad(loss_core, argnums=(0, 1, 2))(q, k, v)
    assert jnp.allclose(lf, lc, rtol=2e-4), (lf, lc)
    for a, b_, name in zip(gf, gc, "qkv"):
        err = jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9)
        assert err < 2e-3, f"d{name} rel err {err}"


def test_flash_untileable_off_tpu_warns_and_runs_core(caplog):
    # a sequence of 64 is under one lane width (heads of 64 dims tile since
    # PR 43, a block of 64 rows does not): off the TPU (toy test models) the
    # core path runs, and says so
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    fa._warn_core_route.cache_clear()
    q, k, v = _make_qkv(jax.random.PRNGKey(1), 1, 64, 64, 2, 2, 64)
    with caplog.at_level("WARNING", logger=fa.logger.name):
        o = flash_attention(q, k, v, causal=True, interpret=True)
    assert "do not tile" in caplog.text and "d=64" in caplog.text
    ref = core_attention(q, k, v, causal=True)
    assert jnp.allclose(o, ref, rtol=1e-5, atol=1e-5)


def test_flash_untileable_raises_on_tpu(monkeypatch):
    # with flash_attention: true on a TPU there is no quiet core route
    from neuronx_distributed_training_tpu.ops import attention as attn_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = _make_qkv(jax.random.PRNGKey(1), 1, 64, 64, 2, 2, 64)
    with pytest.raises(ValueError, match="do not tile"):
        attn_ops.attention(q, k, v, impl="flash")


@pytest.mark.parametrize("nkv", [2, 1], ids=["kv_divides_tp", "kv_below_tp"])
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_flash_impl_on_dp2_tp2_mesh_matches_core(devices8, nkv, masked):
    """``impl="flash"`` on a mesh goes through the shard_map wrap (batch over
    data, heads over model; KV heads repeated when tp exceeds them) — a
    Mosaic call GSPMD cannot partition on a TPU, so parity must hold through
    the wrap and not only through interpret mode's partitionable jnp ops."""
    from conftest import ragged_right_pad_mask

    from neuronx_distributed_training_tpu.ops import attention as attn_ops
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    mesh = build_mesh(MeshConfig(tensor_model_parallel_size=2),
                      devices=devices8[:4])
    b, s = 4, 256
    q, k, v = _make_qkv(jax.random.PRNGKey(3), b, s, s, 4, nkv, 128)
    mask = ragged_right_pad_mask(b, s, [256, 200, 100, 256]) if masked else None

    def loss(impl):
        def f(q, k, v):
            o = attn_ops.attention(q, k, v, impl=impl, attention_mask=mask,
                                   block_q=128, block_kv=128)
            return jnp.sum(o * o)
        return f

    with mesh, shd.use_mesh(mesh):
        lf, gf = jax.jit(jax.value_and_grad(loss("flash"), argnums=(0, 1, 2)))(q, k, v)
        lc, gc = jax.jit(jax.value_and_grad(loss("core"), argnums=(0, 1, 2)))(q, k, v)
    assert jnp.allclose(lf, lc, rtol=2e-4), (lf, lc)
    for a, b_, name in zip(gf, gc, "qkv"):
        err = jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9)
        assert err < 2e-3, f"d{name} rel err {err}"


def test_flash_on_mesh_rejects_heads_tp_cannot_split(devices8):
    from neuronx_distributed_training_tpu.ops import attention as attn_ops
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    mesh = build_mesh(MeshConfig(tensor_model_parallel_size=2),
                      devices=devices8[:4])
    q, k, v = _make_qkv(jax.random.PRNGKey(3), 2, 128, 128, 3, 3, 128)
    with mesh, shd.use_mesh(mesh), pytest.raises(ValueError, match="tp=2"):
        attn_ops.attention(q, k, v, impl="flash")


def test_flash_q_offset_matches_core():
    # context-parallel shard: queries are rows 128..255 of a 256-long sequence
    q, k, v = _make_qkv(jax.random.PRNGKey(2), 1, 128, 256, 2, 2, 128)
    o = flash_attention(
        q, k, v, causal=True, q_offset=128, block_q=128, block_kv=128, interpret=True
    )
    ref = core_attention(q, k, v, causal=True, q_offset=128)
    err = jnp.max(jnp.abs(o - ref))
    assert err < 1e-4, err


def test_flash_bf16_grad_tolerance():
    """Pin bf16 gradient accuracy (dq uses the same fp32 ds accumulation as
    dk/dv — a downcast there showed up as dq-only error growth)."""
    q, k, v = _make_qkv(jax.random.PRNGKey(3), 1, 256, 256, 4, 2, 128, jnp.bfloat16)

    def lf(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                            interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def lc(q, k, v):
        o = core_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(lc, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gc, "qkv"):
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        err = jnp.max(jnp.abs(a32 - b32)) / (jnp.max(jnp.abs(b32)) + 1e-9)
        assert err < 0.05, f"d{name} bf16 rel err {err}"


def _pad_mask(b, skv, valid_lens):
    from tests.conftest import ragged_right_pad_mask

    return ragged_right_pad_mask(b, skv, valid_lens)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_masked_matches_core_fwd_and_grad(causal):
    """Padded-batch (attention_mask) support inside the Pallas kernel: the
    flash path with a key padding mask must match core attention with the
    equivalent additive bias — fwd and all three grads (VERDICT r2 item 2)."""
    from neuronx_distributed_training_tpu.ops.attention import padding_mask_bias

    b, s = 2, 256
    q, k, v = _make_qkv(jax.random.PRNGKey(7), b, s, s, 4, 2, 128)
    mask = _pad_mask(b, s, [s - 37, 129])  # ragged right-padding

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, attention_mask=mask,
            block_q=128, block_kv=128, interpret=True,
        )
        return jnp.sum(o * o)

    def loss_core(q, k, v):
        o = core_attention(q, k, v, causal=causal, bias=padding_mask_bias(mask))
        return jnp.sum(o * o)

    lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    lc, gc = jax.value_and_grad(loss_core, argnums=(0, 1, 2))(q, k, v)
    assert jnp.allclose(lf, lc, rtol=2e-4), (lf, lc)
    for a, b_, name in zip(gf, gc, "qkv"):
        err = jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9)
        assert err < 2e-3, f"d{name} rel err {err}"


def test_flash_masked_no_grad_leak_to_padded_keys():
    """dk/dv on padded key positions must be exactly zero — the backward
    kernels re-apply the padding mask when recomputing p."""
    b, s, valid = 1, 256, 100
    q, k, v = _make_qkv(jax.random.PRNGKey(8), b, s, s, 2, 2, 128)
    mask = _pad_mask(b, s, [valid])

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, attention_mask=mask,
                            block_q=128, block_kv=128, interpret=True)
        return jnp.sum(o * o)

    _, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert jnp.all(dk[:, valid:] == 0), "dk leaks into padded keys"
    assert jnp.all(dv[:, valid:] == 0), "dv leaks into padded keys"


def test_flash_masked_with_lse_matches_core():
    """The lse-exposing variant (ring building block) honors the mask too."""
    from neuronx_distributed_training_tpu.ops.attention import padding_mask_bias
    from neuronx_distributed_training_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )

    b, s = 2, 256
    q, k, v = _make_qkv(jax.random.PRNGKey(9), b, s, s, 2, 2, 128)
    mask = _pad_mask(b, s, [200, 130])
    o, lse = flash_attention_with_lse(
        q, k, v, causal=True, attention_mask=mask,
        block_q=128, block_kv=128, interpret=True,
    )
    ref = core_attention(q, k, v, causal=True, bias=padding_mask_bias(mask))
    assert jnp.max(jnp.abs(o - ref)) < 1e-4
    # lse finite on real rows, NEG_INF convention respected on any fully
    # masked row (none here — row i always sees key i when i < valid)
    assert jnp.all(jnp.isfinite(lse[:, :, :130]))


class TestSegmentedFlash:
    """segment_ids: block-diagonal packed-sequence masking inside the kernel
    (a correctness upgrade over the reference's ConcatDataset, whose packed
    records causally attend ACROSS record boundaries)."""

    def _seg(self, b, s, bounds):
        import numpy as np

        seg = np.zeros((b, s), np.int32)
        for bi in range(b):
            sid = 1
            prev = 0
            for cut in bounds[bi] + [s]:
                seg[bi, prev:cut] = sid
                sid += 1
                prev = cut
        return jnp.asarray(seg)

    @pytest.mark.parametrize("causal", [True, False])
    def test_segmented_matches_core_fwd_and_grad(self, causal):
        from neuronx_distributed_training_tpu.ops.attention import (
            segment_mask_bias,
        )

        b, s = 2, 256
        q, k, v = _make_qkv(jax.random.PRNGKey(20), b, s, s, 4, 2, 128)
        seg = self._seg(b, s, [[100, 180], [37]])

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                                block_q=128, block_kv=128, interpret=True)
            return jnp.sum(o * o)

        def loss_core(q, k, v):
            o = core_attention(q, k, v, causal=causal,
                               bias=segment_mask_bias(seg))
            return jnp.sum(o * o)

        lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        lc, gc = jax.value_and_grad(loss_core, argnums=(0, 1, 2))(q, k, v)
        assert jnp.allclose(lf, lc, rtol=2e-4), (lf, lc)
        for a, b_, name in zip(gf, gc, "qkv"):
            err = jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9)
            assert err < 2e-3, f"d{name} rel err {err}"

    def test_no_cross_segment_leak(self):
        """Changing record 1's tokens must not move record 2's outputs."""
        b, s = 1, 256
        q, k, v = _make_qkv(jax.random.PRNGKey(21), b, s, s, 2, 2, 128)
        seg = self._seg(b, s, [[128]])
        o1 = flash_attention(q, k, v, causal=True, segment_ids=seg,
                             block_q=128, block_kv=128, interpret=True)
        # perturb segment 1 (first 128 positions) of k/v
        k2 = k.at[:, :128].add(1.0)
        v2 = v.at[:, :128].add(-1.0)
        o2 = flash_attention(q, k2, v2, causal=True, segment_ids=seg,
                             block_q=128, block_kv=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(o1[:, 128:]),
                                      np.asarray(o2[:, 128:]))
        assert not np.allclose(np.asarray(o1[:, :128]), np.asarray(o2[:, :128]))

    def test_segments_compose_with_padding_mask(self):
        from neuronx_distributed_training_tpu.ops.attention import (
            padding_mask_bias,
            segment_mask_bias,
        )

        b, s = 1, 256
        q, k, v = _make_qkv(jax.random.PRNGKey(22), b, s, s, 2, 2, 128)
        seg = self._seg(b, s, [[90]])
        mask = _pad_mask(b, s, [200])
        o = flash_attention(q, k, v, causal=True, segment_ids=seg,
                            attention_mask=mask, block_q=128, block_kv=128,
                            interpret=True)
        ref = core_attention(
            q, k, v, causal=True,
            bias=padding_mask_bias(mask) + segment_mask_bias(seg))
        assert jnp.max(jnp.abs(o - ref)) < 1e-4

    def test_cross_attention_segments_rejected(self):
        q, k, v = _make_qkv(jax.random.PRNGKey(23), 1, 128, 256, 2, 2, 128)
        with pytest.raises(ValueError, match="self-attention"):
            flash_attention(q, k, v, causal=False,
                            segment_ids=jnp.zeros((1, 128), jnp.int32),
                            interpret=True)


def test_flash_inside_pipeline_region_matches_core(devices8, tmp_path):
    """pp2 x dp2 x tp2 through the trainer: inside the pipeline's
    pipe-manual region the flash call opens a nested shard_map over the
    remaining axes.  An earlier nested shard_map there (the ring's) summed
    cotangents across pipe — so the gate is the gradient norm, over steps
    that move the parameters, against the same run on core attention."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer
    from test_autotune import tiny_raw

    def run(flash):
        raw = tiny_raw(tp=2, pp=2, sched="1f1b", h=256, heads=2, kv=2,
                       ffn=512, seq=128, layers=4, gbs=8,
                       fusions={"flash_attention": flash})
        raw["trainer"]["max_steps"] = 3
        raw["exp_manager"] = {"exp_dir": str(tmp_path / f"flash_{flash}"),
                              "create_checkpoint_callback": False}
        return Trainer.from_config(load_config(raw), devices=devices8,
                                   enable_checkpointing=False).fit()

    flash, core = run(True), run(False)
    assert flash["loss"] == pytest.approx(core["loss"], rel=2e-3)
    assert flash["grad_norm"] == pytest.approx(core["grad_norm"], rel=5e-3)


# ---------------------------------------------------------------------------
# the band: the innermost grid dimension covers only the blocks that
# causal / window / q_offset can show (ops/flash_attention.py::_band)
# ---------------------------------------------------------------------------


def _walks_agree(q, k, v, *, causal, window, q_offset=0, bq=128, bkv=256,
                 mask=None, seg=None, with_dlse=False, narrower=True):
    """fwd, dq and dkv over the call's band against the same kernels walking
    the whole range from block 0 (the walk before the band): same visible
    pairs in the same order, so every output must be EQUAL, not close."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    b, sq, nh, d = q.shape
    skv = k.shape[1]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    kvm = fa._prep_rows(mask, b, skv, "attention_mask")
    segr = fa._prep_rows(seg, b, sq, "segment_ids")
    num_q, num_kv = sq // bq, skv // bkv
    band = fa._band(bq, bkv, num_q, num_kv, causal, window, q_offset)
    full = fa._band(bq, bkv, num_q, num_kv, False, None, 0)
    assert (full.kv, full.q) == (num_kv, num_q)
    assert band.kv <= num_kv and band.q <= num_q
    assert (band.kv < num_kv) == narrower, band
    common = dict(sm_scale=d ** -0.5, causal=causal, window=window,
                  q_offset=q_offset, bq=bq, bkv=bkv, interpret=True)
    kg, kl = jax.random.split(jax.random.PRNGKey(31))
    g = jax.random.normal(kg, qt.shape[:3] + vt.shape[3:], q.dtype)   # o's shape
    dlse = jax.random.normal(kl, qt.shape[:3], jnp.float32) if with_dlse else None
    outs = []
    for walk in (band, full):
        o, lse = fa._fwd_pallas(qt, kt, vt, kvm, segr, band=walk, **common)
        dq, dk, dv = fa._bwd_pallas((qt, kt, vt, kvm, segr, o, lse), g,
                                    dlse=dlse, band=walk, **common)
        outs.append((o, lse, dq, dk, dv))
    for name, a, e in zip(("o", "lse", "dq", "dk", "dv"), *outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e), err_msg=name)
    return outs[0]


BAND_CASES = {
    # name: (nh, nkv, bq, bkv, causal, window, narrower than the full range)
    "window_8_key_blocks": (2, 2, 128, 256, True, 512, True),
    "window_16_key_blocks": (2, 2, 128, 128, True, 300, True),
    "window_covers_seq": (2, 2, 128, 256, True, 2048, False),
    "no_window": (2, 2, 128, 256, True, None, False),
    "gqa_group_4": (4, 1, 128, 256, True, 512, True),
}


@pytest.mark.parametrize("case", BAND_CASES, ids=list(BAND_CASES))
def test_band_walk_equals_full_walk(case):
    nh, nkv, bq, bkv, causal, window, narrower = BAND_CASES[case]
    q, k, v = _make_qkv(jax.random.PRNGKey(17), 1, 2048, 2048, nh, nkv, 128,
                        jnp.bfloat16)
    _walks_agree(q, k, v, causal=causal, window=window, bq=bq, bkv=bkv,
                 narrower=narrower)


@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (128, 256)])
def test_band_walk_equals_full_walk_where_score_dims_are_not_value_dims(d_qk, d_v):
    """Latent attention's call (models/kanana.py): q and k of ``d_qk``, v, o and
    do of ``d_v``; dq and dk come out ``d_qk`` wide, dv ``d_v``."""
    q, k, _ = _make_qkv(jax.random.PRNGKey(23), 1, 1024, 1024, 2, 2, d_qk, jnp.bfloat16)
    *_, v = _make_qkv(jax.random.PRNGKey(24), 1, 1024, 1024, 2, 2, d_v, jnp.bfloat16)
    o, lse, dq, dk, dv = _walks_agree(q, k, v, causal=True, window=300, bq=128, bkv=128)
    assert o.shape == dv.shape == (1, 2, 1024, d_v)
    assert dq.shape == dk.shape == (1, 2, 1024, d_qk) and lse.shape[:3] == (1, 2, 1024)


@pytest.mark.parametrize("rows", ["attention_mask", "segment_ids"])
def test_band_walk_equals_full_walk_with_row_predicates(rows):
    """The padding-mask and segment predicates stay what they were: the band
    comes from causal / window / q_offset alone and they predicate inside it."""
    b, s = 2, 2048
    q, k, v = _make_qkv(jax.random.PRNGKey(19), b, s, s, 2, 1, 128, jnp.bfloat16)
    pos = jnp.arange(s)[None, :]
    if rows == "attention_mask":  # padded tails: whole key blocks of padding
        given = dict(mask=(pos < jnp.array([[1500], [700]])).astype(jnp.int32))
    else:  # packed records of uneven length
        given = dict(seg=((pos >= 300).astype(jnp.int32)
                          + (pos >= jnp.array([[1100], [1900]]))))
    _walks_agree(q, k, v, causal=True, window=512, **given)


def test_band_walk_survives_query_blocks_that_see_nothing():
    """A ring step's chunk (non-causal, window at a static q_offset): the late
    query blocks see no key block at all, their steps are all clamped, and they
    come out as output 0 with lse NEG_INF, as before."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    q, k, v = _make_qkv(jax.random.PRNGKey(23), 1, 2048, 2048, 2, 2, 128,
                        jnp.bfloat16)
    o, lse, dq, _, _ = _walks_agree(
        q, k, v, causal=False, window=512, q_offset=1536, with_dlse=True)
    # key kv_pos is seen iff kv_pos > q_pos + 1536 - 512: rows from 1023 on see none
    assert jnp.all(lse[0, :, 1023:, 0] == fa.NEG_INF)
    assert jnp.all(o[0, :, 1023:] == 0) and jnp.all(dq[0, :, 1023:] == 0)
    assert jnp.all(lse[0, :, :1023, 0] > fa.NEG_INF / 2)
    # and through the public entry, against core attention on the seen rows
    o_pub, lse_pub = fa.flash_attention_with_lse(
        q, k, v, causal=False, sliding_window=512, q_offset=1536,
        block_q=128, block_kv=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(jnp.swapaxes(o_pub, 1, 2)),
                                  np.asarray(o))
    # core_attention has no window without causal: the plain softmax, masked
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * 128 ** -0.5
    seen = jnp.arange(2048)[None, :] > jnp.arange(2048)[:, None] + 1536 - 512
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf)[:, :, :1023], axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    assert jnp.max(jnp.abs(o_pub[:, :1023].astype(jnp.float32) - ref)) < 2e-2


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "non_causal"])
@pytest.mark.parametrize("window", [None, 1, 100, 128, 300, 512, 5000])
def test_band_spans_are_exactly_what_visible_admits(causal, window):
    """The helper alone, over a lattice of shapes: a block's span is the set
    of partners ``_visible`` admits (so every visible pair is walked), the
    band is the longest span, and never longer than the full range."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    for sq, skv, bq, bkv, q_offset in itertools.product(
            (256, 512, 1024), (256, 512, 1024), (128, 256), (128, 256, 512),
            (-300, 0, 128, 300, 1024, 4096)):
        if skv % bkv:
            continue
        num_q, num_kv = sq // bq, skv // bkv
        band = fa._band(bq, bkv, num_q, num_kv, causal, window, q_offset)
        vis = np.asarray(fa._visible(
            np.arange(num_q)[:, None], np.arange(num_kv)[None, :], bq, bkv,
            causal, window, q_offset)) & np.ones((num_q, num_kv), bool)
        at = dict(sq=sq, skv=skv, bq=bq, bkv=bkv, q_offset=q_offset)
        for qi in range(num_q):
            first, last = fa._kv_span(band, qi)
            assert list(np.flatnonzero(vis[qi])) == list(range(first, last + 1)), (at, qi)
        for ki in range(num_kv):
            first, last = fa._q_span(band, ki)
            assert list(np.flatnonzero(vis[:, ki])) == list(range(first, last + 1)), (at, ki)
        assert band.kv == max(1, vis.sum(1).max()) <= num_kv, at
        assert band.q == max(1, vis.sum(0).max()) <= num_q, at


def test_band_at_the_32k_cells_shape():
    """mistral7b-pretrain-32k: seq 32768, tiles 512 x 2048, window 4096."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    bq, bkv = fa._block_sizes(32768, 32768, None, None)
    assert (bq, bkv) == (512, 2048)
    band = fa._band(bq, bkv, 64, 16, True, 4096, 0)
    assert (band.kv, band.q) == (3, 12)
    # the 4k cells: window = sequence or none, 2 key blocks: the full extent
    for window in (4096, None):
        band = fa._band(bq, bkv, 8, 2, True, window, 0)
        assert (band.kv, band.q) == (2, 8)


# ---------------------------------------------------------------------------
# the diagonal walk: a causal window no wider than the (square) tile, with no
# row predicates, takes one grid step a query block (ops/flash_attention.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("group", [1, 3], ids=["mha", "gqa_group_3"])
@pytest.mark.parametrize("tile, window", [
    (512, 512), (512, 256), (512, 128), (128, 128)],
    ids=lambda x: str(x))
def test_diagonal_walk_matches_core_and_the_band_walk(tile, window, group):
    """Forward, lse and the three gradients in float32 under ``interpret``:
    against ``core_attention`` and against the band walk of the same call
    (close, not equal: the softmax is taken in one pass and the sums run in
    another order).  Three query blocks: the first, whose rows see fewer keys
    than the window, and two that look back into the block before them."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    s = 3 * tile
    q, k, v = _make_qkv(jax.random.PRNGKey(41), 1, s, s, group, 1, 128)
    g = jax.random.normal(jax.random.PRNGKey(42), q.shape, q.dtype)
    dlse = jax.random.normal(jax.random.PRNGKey(43), (1, group, s), jnp.float32)
    qt, kt, vt, gt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, g))
    args = (qt, kt, None, None, True, window, 0, tile, tile)
    assert fa._takes_diagonal(*args)
    common = dict(sm_scale=128 ** -0.5, window=window, bq=tile, interpret=True)
    o, lse = fa._diag_fwd(qt, kt, vt, **common)
    grads = fa._diag_bwd((qt, kt, vt, None, None, o, lse), gt, dlse=dlse, **common)
    band = dict(common, causal=True, q_offset=0, bkv=tile)
    o_b, lse_b = fa._fwd_pallas(qt, kt, vt, None, None, **band)
    grads_b = fa._bwd_pallas((qt, kt, vt, None, None, o_b, lse_b), gt, dlse=dlse, **band)
    for name, a, e in zip(("o", "lse", "dq", "dk", "dv"),
                          (o, lse, *grads), (o_b, lse_b, *grads_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=2e-5,
                                   atol=2e-5, err_msg=name)

    # the reference: softmax over the window's keys, its logsumexp as a
    # second output so that dlse is exercised too
    def core(q, k, v):
        o = core_attention(q, k, v, causal=True, sliding_window=window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, 2)) * 128 ** -0.5
        pos = jnp.arange(s)
        seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
        return o, jax.nn.logsumexp(jnp.where(seen, scores, -jnp.inf), axis=-1)

    (o_c, lse_c), vjp = jax.vjp(core, q, k, v)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(o, 1, 2)), np.asarray(o_c),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(lse_c),
                               rtol=2e-5, atol=2e-5)
    for name, a, e in zip("qkv", grads, vjp((g, dlse))):
        a = jnp.swapaxes(a, 1, 2)
        err = jnp.max(jnp.abs(a - e)) / jnp.max(jnp.abs(e))
        assert err < 1e-5, f"d{name} rel err {err}"


#: the Laguna window layers' call at a third of its heads and a quarter of its
#: sequence, and one departure from it a case: (nh, nkv, sq, skv, window,
#: block_kv, q_offset, attention_mask, segment_ids) -> the walk
_LAGUNA = dict(nh=18, nkv=2, sq=2048, skv=2048, window=512, block_kv=512,
               q_offset=0, mask=False, seg=False)
WALK_CASES = {
    "laguna_window_shape": ({}, "diagonal"),  # sub-tiles of 256: 6 of the band's 8
    "window_narrower_than_tile": ({"window": 200}, "diagonal"),  # 4 of 8
    "window_wider_than_tile": ({"window": 513}, "band"),
    "key_tile_not_the_query_tile": ({"block_kv": 1024}, "band"),
    "attention_mask": ({"mask": True}, "band"),
    "segment_ids": ({"seg": True}, "band"),
    "q_offset": ({"q_offset": 512}, "band"),
    "sq_not_skv": ({"sq": 1024}, "band"),
    "no_window": ({"window": None}, "band"),
}


@pytest.mark.parametrize("case", WALK_CASES, ids=list(WALK_CASES))
def test_the_call_chooses_its_walk(case):
    """The choice is made from the call's own arguments, above the kernels:
    the band walk's kernels take a grid with the band as its innermost
    dimension (4 for fwd and dq, 5 for dkv), the diagonal walk's have none
    (4 each, with K and V handed to fwd and dq twice); ``flash_band`` of the
    trace's facts says which, and what share of a band's sub-tiles."""
    from neuronx_distributed_training_tpu.ops import flash_attention as fa
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    change, walk = WALK_CASES[case]
    c = dict(_LAGUNA, **change)
    q = jax.ShapeDtypeStruct((1, c["sq"], c["nh"], 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, c["skv"], c["nkv"], 128), jnp.bfloat16)
    rows = jnp.ones((1, c["skv"]), jnp.int32)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, sliding_window=c["window"], q_offset=c["q_offset"],
            attention_mask=rows if c["mask"] else None,
            segment_ids=rows if c["seg"] else None,
            block_kv=c["block_kv"], interpret=True).astype(jnp.float32))

    with shd.collect_trace_facts() as facts:
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    (fact,) = facts["flash_band"]
    assert fact["walk"] == walk
    assert fact.get("sub_tiles") == {
        "laguna_window_shape": [6, 8], "window_narrower_than_tile": [4, 8]}.get(case)
    calls = {}

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = (
                    len(eqn.params["grid_mapping"].grid), len(eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(jaxpr.jaxpr)
    extra = int(c["mask"]) + 2 * int(c["seg"])
    assert calls == {
        "band": {"flash_fwd": (4, 3 + extra), "flash_dq": (4, 6 + extra),
                 "flash_dkv": (5, 6 + extra)},
        "diagonal": {"flash_fwd": (4, 5), "flash_dq": (4, 8), "flash_dkv": (4, 10)},
    }[walk], calls


# -- heads of half a lane width (models/lfm2.py) ---------------------------------


@pytest.mark.parametrize("window", [None, 300], ids=["causal", "window_300"])
def test_band_walk_equals_full_walk_at_64_dim_heads(window):
    """Heads of 64 dims are fed as they are (blocks 64 wide): the same walk,
    bit for bit the walk over the whole range, 4 query heads a key/value head."""
    q, k, v = _make_qkv(jax.random.PRNGKey(29), 1, 1024, 1024, 8, 2, 64, jnp.bfloat16)
    o, lse, dq, dk, dv = _walks_agree(q, k, v, causal=True, window=window, bq=128, bkv=128,
                                      narrower=window is not None)
    assert o.shape == dq.shape == (1, 8, 1024, 64) and dk.shape == dv.shape == (1, 2, 1024, 64)


def test_what_the_kernels_take_at_half_a_lane_width_and_what_they_say():
    from neuronx_distributed_training_tpu.ops import flash_attention as fa
    from neuronx_distributed_training_tpu.parallel import sharding as shd

    assert fa.flash_tileable(8192, 8192, 64, 32, 8)              # the LFM2 cell's call
    assert not fa.flash_tileable(8192, 8192, 32, 32, 8)          # a quarter lane: no
    assert not fa.flash_tileable(8192, 8192, 192, 32, 32)        # one head dim past a lane: whole lanes
    assert not fa.flash_tileable(8192, 8192, 64, 32, 8, d_v=96)
    assert not fa.flash_tileable(8192, 8192, 64, 32, 6)          # heads that do not group
    # half-lane heads take a square tile of 1024 (measured: PERF.md section 6, PR 43)
    assert fa._block_sizes(8192, 8192, None, None, jnp.bfloat16, 64) == (1024, 1024)
    assert fa._block_sizes(8192, 8192, None, None, jnp.float32, 64) == (1024, 512)
    assert fa._block_sizes(512, 512, None, None, jnp.bfloat16, 64) == (512, 512)
    assert fa._block_sizes(8192, 8192, 512, 2048, jnp.bfloat16, 64) == (512, 2048)
    # what a multiple of 128 is tiled to stays what it was
    assert fa._block_sizes(8192, 8192, None, None, jnp.bfloat16, 128) == (512, 2048)
    assert fa._block_sizes(8192, 8192, None, None, jnp.bfloat16, 192) == (512, 1024)
    q64 = jnp.zeros((1, 256, 4, 64), jnp.float32)
    kv64 = jnp.zeros((1, 256, 1, 64), jnp.float32)
    q128 = jnp.zeros((1, 256, 2, 128), jnp.float32)
    with shd.collect_trace_facts() as facts:
        jax.eval_shape(lambda: flash_attention(q64, kv64, kv64, block_q=128, block_kv=128,
                                               interpret=True))
        jax.eval_shape(lambda: flash_attention(q128, q128, q128, block_q=128, block_kv=128,
                                               interpret=True))
    half, whole = facts["flash_band"]
    assert (half["d"], half["feed"], half["walk"]) == (64, "whole", "band")
    assert not {"d", "d_qk", "d_v", "feed"} & set(whole)
    # a window no wider than the tile keeps the band walk at 64 dims: the
    # diagonal walk's kernels know heads of whole lane widths
    qt = jnp.zeros((1, 4, 256, 64))
    assert fa._takes_diagonal(qt, qt, None, None, True, 128, 0, 128, 128)
    assert not fa._one_head_dim(qt, qt) and fa._one_head_dim(jnp.zeros((1, 2, 256, 128)),
                                                              jnp.zeros((1, 2, 256, 128)))
    with shd.collect_trace_facts() as facts:
        jax.eval_shape(lambda: flash_attention(q64, kv64, kv64, sliding_window=128,
                                               block_q=128, block_kv=128, interpret=True))
    assert facts["flash_band"][0]["walk"] == "band"
