"""Zig-zag (balanced causal) ring attention: numerics + trainer wiring.

The reference's NKI ring kernel uses the contiguous layout and carries the
causal-ring imbalance; the zig-zag layout (rank r holds chunks r and 2cp-1-r)
equalizes per-rank causal work.  Not in the reference — a TPU-native extension.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_training_tpu.ops.attention import core_attention
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.parallel.ring_attention import (
    zigzag_positions,
    zigzag_ring_attention,
    zigzag_transform_batch,
)

import pytest as _pytest_mark

pytestmark = _pytest_mark.mark.slow  # multi-minute parity tests


@pytest.fixture(scope="module")
def cp_mesh():
    return build_mesh(MeshConfig(context_parallel_size=4))


def make_qkv(key, b=2, s=64, h=4, kvh=None, d=16):
    kvh = kvh or h
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, kvh, d), jnp.float32),
            jax.random.normal(kv, (b, s, kvh, d), jnp.float32))


class TestZigzagLayout:
    def test_positions_partition(self):
        pos = np.asarray(zigzag_positions(32, 4))
        assert sorted(pos.tolist()) == list(range(32))
        # rank 0's slots hold chunks 0 and 7
        assert pos[:4].tolist() == [0, 1, 2, 3]
        assert pos[4:8].tolist() == [28, 29, 30, 31]

    def test_cp1_identity(self):
        pos = np.asarray(zigzag_positions(16, 1))
        np.testing.assert_array_equal(pos, np.arange(16))

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="divide"):
            zigzag_positions(30, 4)

    def test_transform_shifts_then_permutes(self):
        ids = jnp.arange(16, dtype=jnp.int32)[None, :]
        out = zigzag_transform_batch({"input_ids": ids, "labels": ids}, cp=2)
        pos = np.asarray(zigzag_positions(16, 2))
        np.testing.assert_array_equal(np.asarray(out["input_ids"][0]), pos)
        # label at slot p = original next token, -100 at the original final pos
        expect = np.where(pos + 1 < 16, pos + 1, -100)
        np.testing.assert_array_equal(np.asarray(out["labels"][0]), expect)


class TestZigzagNumerics:
    def test_matches_core(self, cp_mesh):
        q, k, v = make_qkv(jax.random.PRNGKey(0))
        pos = zigzag_positions(64, 4)
        inv = jnp.argsort(pos)
        ref = core_attention(q, k, v, causal=True)
        qz, kz, vz = (jnp.take(x, pos, axis=1) for x in (q, k, v))
        with cp_mesh, shd.use_mesh(cp_mesh):
            oz = jax.jit(lambda *a: zigzag_ring_attention(*a))(qz, kz, vz)
        np.testing.assert_allclose(
            np.asarray(jnp.take(oz, inv, axis=1)), np.asarray(ref), atol=2e-5)

    def test_grads_match_core(self, cp_mesh):
        q, k, v = make_qkv(jax.random.PRNGKey(1), s=32)
        pos = zigzag_positions(32, 4)

        def loss_zz(q, k, v):
            qz, kz, vz = (jnp.take(x, pos, axis=1) for x in (q, k, v))
            return jnp.sum(jnp.square(zigzag_ring_attention(qz, kz, vz)))

        def loss_core(q, k, v):
            return jnp.sum(jnp.square(core_attention(q, k, v, causal=True)))

        ref_g = jax.grad(loss_core, argnums=(0, 1, 2))(q, k, v)
        with cp_mesh, shd.use_mesh(cp_mesh):
            g = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
        for a, r in zip(g, ref_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-4)

    def test_gqa(self, cp_mesh):
        q, k, v = make_qkv(jax.random.PRNGKey(2), h=8, kvh=2)
        pos = zigzag_positions(64, 4)
        inv = jnp.argsort(pos)
        ref = core_attention(q, k, v, causal=True)
        qz, kz, vz = (jnp.take(x, pos, axis=1) for x in (q, k, v))
        with cp_mesh, shd.use_mesh(cp_mesh):
            oz = jax.jit(lambda *a: zigzag_ring_attention(*a))(qz, kz, vz)
        np.testing.assert_allclose(
            np.asarray(jnp.take(oz, inv, axis=1)), np.asarray(ref), atol=2e-5)

    def test_non_causal_rejected(self, cp_mesh):
        q, k, v = make_qkv(jax.random.PRNGKey(3), s=32)
        with cp_mesh, shd.use_mesh(cp_mesh):
            with pytest.raises(ValueError, match="causal-only"):
                zigzag_ring_attention(q, k, v, causal=False)


    def test_with_tp_and_cp(self):
        """zig-zag composes with TP (heads over model) + GQA replication."""
        mesh = build_mesh(MeshConfig(context_parallel_size=2,
                                     tensor_model_parallel_size=2))
        q, k, v = make_qkv(jax.random.PRNGKey(9), h=4, kvh=2)
        pos = zigzag_positions(64, 2)
        inv = jnp.argsort(pos)
        ref = core_attention(q, k, v, causal=True)
        qz, kz, vz = (jnp.take(x, pos, axis=1) for x in (q, k, v))
        with mesh, shd.use_mesh(mesh):
            oz = jax.jit(lambda *a: zigzag_ring_attention(*a))(qz, kz, vz)
        np.testing.assert_allclose(
            np.asarray(jnp.take(oz, inv, axis=1)), np.asarray(ref), atol=2e-5)


class TestZigzagTrainer:
    def test_loss_matches_contiguous_ring(self, devices8):
        """The full trainer loss hook (permute + pre-shift + positions) under
        zig-zag equals the contiguous-ring loss on the same batch."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.models.family import resolve
        from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

        fp32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                           softmax_dtype=jnp.float32)
        base = {
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
            "num_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "max_position_embeddings": 64,
            "activations_checkpoint_granularity": None,
        }
        ds = {"context_parallel_size": 4}
        cfg_zz = load_config({
            "model": {**base, "fusions": {"zigzag_ring_attention": True}},
            "distributed_strategy": ds,
        })
        cfg_ring = load_config({
            "model": {**base, "fusions": {"ring_attention": True}},
            "distributed_strategy": ds,
        })
        mesh = build_mesh(MeshConfig(context_parallel_size=4))
        ids = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 128)
        batch = {"input_ids": ids, "labels": ids}

        (fam, mc_z), (_, mc_r) = resolve(cfg_zz), resolve(cfg_ring)
        loss_z, loss_r = fam.loss(mc_z, fp32), fam.loss(mc_r, fp32)
        params = fam.init_params(jax.random.PRNGKey(0), mc_z, fp32)
        with mesh, shd.use_mesh(mesh):
            lz, _ = jax.jit(loss_z)(params, batch, None)
            lr, _ = jax.jit(loss_r)(params, batch, None)
        np.testing.assert_allclose(float(lz), float(lr), rtol=1e-5)

    def test_trainer_end_to_end(self, tmp_path, devices8):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = load_config({
            "name": "zz", "model_source": "hf", "seed": 3,
            "trainer": {"max_steps": 2, "log_every_n_steps": 1},
            "exp_manager": {"exp_dir": str(tmp_path / "exp")},
            "distributed_strategy": {"context_parallel_size": 4},
            "data": {"global_batch_size": 4, "micro_batch_size": 1,
                     "seq_length": 64, "synthetic": True},
            "model": {
                "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
                "num_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "max_position_embeddings": 64,
                "fusions": {"zigzag_ring_attention": True},
                "optim": {"name": "adamw_fp32OptState", "lr": 1e-3,
                          "sched": {"name": "LinearAnnealingWithWarmUp",
                                    "warmup_steps": 1, "max_steps": 2}},
            },
            "precision": {"type": "mixed_precision"},
        })
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        m = t.fit()
        assert np.isfinite(m["loss"])

    def test_pp_guard(self, tmp_path, devices8):
        """zigzag + pp is rejected by the load-time catalog (round 3 moved
        the guard from Trainer.from_config to validate_config — it now dies
        before any compilation)."""
        from neuronx_distributed_training_tpu.config.loader import load_config

        with pytest.raises(ValueError, match="zigzag_ring_attention"):
            load_config({
            "name": "zzpp", "model_source": "hf", "seed": 3,
            "trainer": {"max_steps": 1},
            "exp_manager": {"exp_dir": str(tmp_path / "exp")},
            "distributed_strategy": {"context_parallel_size": 2,
                                     "pipeline_model_parallel_size": 2},
            "data": {"global_batch_size": 4, "micro_batch_size": 1,
                     "seq_length": 32, "synthetic": True},
            "model": {
                "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
                "num_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "max_position_embeddings": 32,
                "fusions": {"zigzag_ring_attention": True},
                "optim": {"lr": 1e-3},
            },
            "precision": {"type": "mixed_precision"},
            })
