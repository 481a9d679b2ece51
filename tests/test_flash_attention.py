"""Numerics gate for the Pallas flash-attention kernel: forward and gradients
must match core_attention (the reference-numerics implementation) in
interpreter mode on CPU (SURVEY.md §4 plan item (a))."""

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
    # head_dim 64 is not lane-aligned: off the TPU (toy test models) the
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
