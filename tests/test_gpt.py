"""Megatron-family GPT: config surface, forward variants, TP parity, dropout."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.models import gpt
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

FP32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                   softmax_dtype=jnp.float32)

BASE = dict(
    vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4,
    max_position_embeddings=32, activations_checkpoint_granularity=None,
)


def _batch(key, b=2, s=16, vocab=96):
    ids = jax.random.randint(key, (b, s), 0, vocab)
    return {"input_ids": ids, "labels": ids}


class TestVariants:
    @pytest.mark.parametrize("kwargs", [
        dict(),  # gelu + layernorm + learned bias + rope + tied
        dict(activation="swiglu", normalization="rmsnorm", bias=False),
        dict(position_embedding_type="learned_absolute"),
        dict(num_query_groups=2),
        dict(num_query_groups=1),  # MQA
        dict(rotary_percentage=0.5),
        dict(share_embeddings_and_output_weights=False),
        dict(sliding_window=8),
    ])
    def test_forward_finite(self, kwargs):
        cfg = gpt.GPTConfig(**{**BASE, **kwargs})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        loss, _ = gpt.forward(params, _batch(jax.random.PRNGKey(1)), cfg, FP32)
        assert np.isfinite(float(loss))
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5

    def test_moe_gpt(self):
        cfg = gpt.GPTConfig(**BASE, moe=moe_ops.MoEConfig(
            num_experts=4, top_k=1, router_type="sinkhorn", dropless=True))
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        loss, aux = gpt.forward(params, _batch(jax.random.PRNGKey(1)), cfg, FP32)
        assert np.isfinite(float(loss))
        assert "router_aux_loss" in aux

    def test_dropout_deterministic_given_rng(self):
        cfg = gpt.GPTConfig(**BASE, hidden_dropout=0.2, embedding_dropout=0.1)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1))
        l1, _ = gpt.forward(params, batch, cfg, FP32, rng=jax.random.PRNGKey(7))
        l2, _ = gpt.forward(params, batch, cfg, FP32, rng=jax.random.PRNGKey(7))
        l3, _ = gpt.forward(params, batch, cfg, FP32, rng=jax.random.PRNGKey(8))
        assert float(l1) == float(l2)
        assert float(l1) != float(l3)
        # eval mode (no rng) = no dropout
        le, _ = gpt.forward(params, batch, cfg, FP32)
        assert float(le) != float(l1)

    def test_from_config_megatron_schema(self):
        cfg = gpt.GPTConfig.from_config({
            "vocab_size": 1000, "hidden_size": 64, "num_layers": 4,
            "num_attention_heads": 8, "num_query_groups": 2,
            "activation": "swiglu", "normalization": "rmsnorm",
            "position_embedding_type": "rope", "bias": False,
            "num_moe_experts": 8,
        }, {"sequence_parallel": True, "tensor_model_parallel_size": 2})
        assert cfg.kv_heads == 2
        assert cfg.is_glu
        assert cfg.moe is not None and cfg.moe.num_experts == 8
        assert cfg.sequence_parallel


@pytest.mark.slow
class TestShardedGPT:
    def test_tp_parity(self, devices8):
        cfg = gpt.GPTConfig(**BASE, num_query_groups=2, activation="swiglu")
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1), b=4)  # divisible by the dp axis (4)

        def loss_fn(p, b):
            return gpt.forward(p, b, cfg, FP32)[0]

        ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)
        mesh = build_mesh(MeshConfig(tensor_model_parallel_size=2))
        specs = gpt.param_specs(cfg)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        sh_batch = jax.device_put(batch, ns(P(("data", "expert"))))
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(sh_params, sh_batch)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(grads["embed"]["embedding"]),
            np.asarray(ref_grads["embed"]["embedding"]), rtol=1e-3, atol=1e-5,
        )

    def test_moe_sinkhorn_ep_parity(self, devices8):
        """GPT-MoE with the documented sinkhorn top-1 dropless router on a
        dp2 x ep4 mesh against one device.  Sinkhorn normalises over the whole
        token set: routing has to see the global tokens even though the
        expert block runs per token shard (ops/moe.py::_dropless_on_mesh)."""
        cfg = gpt.GPTConfig(**BASE, activation="swiglu", moe=moe_ops.MoEConfig(
            num_experts=4, top_k=1, router_type="sinkhorn", dropless=True))
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1), b=8)

        def loss_fn(p, b):
            return gpt.forward(p, b, cfg, FP32)[0]

        ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)
        mesh = build_mesh(MeshConfig(expert_model_parallel_size=4))
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(params, jax.tree_util.tree_map(
            ns, gpt.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)))
        sh_batch = jax.device_put(batch, ns(P(("data", "expert"))))
        with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(sh_params, sh_batch)
        # dp2 x ep4, 4 experts, one row of 16 tokens a chip, one choice each:
        # rows travel up to twice the fair 16 (a chip could receive 64)
        assert traced == {"moe_token_shards": 8, "moe_expert_exchange": "tokens",
                          "moe_row_bounds": [32],
                          "remat": {"layers": {"granularity": None, "kept": "all"}}}
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                        jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-5)

    def test_pipeline_specs_exist(self):
        cfg = gpt.GPTConfig(**BASE)
        specs = gpt.param_specs(cfg, pipeline=True)
        assert specs["layers"]["attn"]["qkv"]["w"][0] == "pipe"


class TestGPTAttentionMask:
    def test_left_padded_matches_unpadded(self):
        from neuronx_distributed_training_tpu.models import gpt as gpt_mod
        from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

        fp32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                           softmax_dtype=jnp.float32)
        for pe in ("rope", "learned_absolute"):
            cfg = gpt_mod.GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_position_embeddings=32,
                position_embedding_type=pe,
                activations_checkpoint_granularity=None,
            )
            params = gpt_mod.init_params(jax.random.PRNGKey(0), cfg, fp32)
            ids = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 3, 64)
            ref, _ = gpt_mod.forward(params, {"input_ids": ids}, cfg, fp32)
            pad = 4
            padded = jnp.concatenate([jnp.zeros((1, pad), ids.dtype), ids], 1)
            mask = jnp.concatenate(
                [jnp.zeros((1, pad), jnp.int32), jnp.ones((1, 12), jnp.int32)], 1)
            out, _ = gpt_mod.forward(
                params, {"input_ids": padded, "attention_mask": mask}, cfg, fp32)
            np.testing.assert_allclose(
                np.asarray(out[:, pad:]), np.asarray(ref), rtol=2e-5, atol=2e-5,
                err_msg=f"position_embedding_type={pe}")

    def test_mask_folds_into_loss(self):
        from neuronx_distributed_training_tpu.models import gpt as gpt_mod
        from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

        fp32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                           softmax_dtype=jnp.float32)
        cfg = gpt_mod.GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_attention_heads=4,
            max_position_embeddings=32, activations_checkpoint_granularity=None,
        )
        params = gpt_mod.init_params(jax.random.PRNGKey(0), cfg, fp32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 3, 64)
        mask = jnp.ones((2, 16), jnp.int32).at[:, :6].set(0)
        loss_a, _ = gpt_mod.forward(
            params, {"input_ids": ids, "labels": ids, "attention_mask": mask},
            cfg, fp32)
        loss_b, _ = gpt_mod.forward(
            params, {"input_ids": ids, "labels": ids, "attention_mask": mask,
                     "loss_mask": mask.astype(jnp.float32)}, cfg, fp32)
        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)


class TestGPTMoEFrequency:
    """Dense/MoE interleave for the megatron family
    (reference megatron_gpt_model.py:137 moe_frequency)."""

    def _cfg(self, freq, dropout=0.0):
        from neuronx_distributed_training_tpu.ops import moe as moe_ops

        return gpt.GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=4, num_attention_heads=4,
            max_position_embeddings=32, hidden_dropout=dropout,
            activations_checkpoint_granularity=None,
            moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                                  router_aux_loss_coef=0.02),
            moe_frequency=freq,
        )

    @pytest.mark.slow
    def test_interleaved_structure_and_training(self):
        cfg = self._cfg(2)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        assert "moe" in params["layers"]["mlp"] and "dense" in params["layers"]["mlp"]
        assert params["layers"]["mlp"]["moe"]["router"]["w"].shape[0] == 2  # G
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        batch = {"input_ids": ids, "labels": ids}

        def loss_fn(p):
            return gpt.forward(p, batch, cfg, FP32)[0]

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        assert float(np.abs(np.asarray(
            grads["layers"]["mlp"]["moe"]["router"]["w"])).max()) > 0
        assert float(np.abs(np.asarray(
            grads["layers"]["mlp"]["dense"]["up"]["w"])).max()) > 0
        # specs tree matches the param tree
        specs = gpt.param_specs(cfg)
        assert (jax.tree_util.tree_structure(params)
                == jax.tree_util.tree_structure(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec)))

    def test_interleaved_dropout_runs(self):
        cfg = self._cfg(2, dropout=0.1)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        loss, _ = gpt.forward(params, {"input_ids": ids, "labels": ids}, cfg,
                              FP32, rng=jax.random.PRNGKey(7))
        assert np.isfinite(float(loss))

    def test_aux_normalized_over_moe_layers(self):
        cfg = self._cfg(2)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        _, aux = gpt.forward(params, {"input_ids": ids, "labels": ids}, cfg, FP32)
        # coefficient-weighted per-layer mean >= coef * 1.0 lower bound
        assert float(aux["router_aux_loss"]) >= 0.02

    def test_indivisible_raises(self):
        cfg = self._cfg(3)
        with pytest.raises(ValueError, match="frequency"):
            gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)

    @pytest.mark.slow  # fit()-based; 40 s — keeps the CI fast tier < 5 min
    def test_interleave_under_pp_trains(self, devices8):
        """gpt + moe_frequency>1 + pp=2 now trains end-to-end (grouped stage
        slicing); one fit() step produces a finite loss."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = load_config({
            "name": "t", "model_source": "megatron", "seed": 1,
            "trainer": {"max_steps": 1},
            "distributed_strategy": {"pipeline_model_parallel_size": 2,
                                     "tensor_model_parallel_size": 2},
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 16, "synthetic": True},
            "model": {"architecture": "gpt", "vocab_size": 64,
                      "hidden_size": 32, "num_layers": 4,
                      "num_attention_heads": 4, "max_position_embeddings": 16,
                      "moe": {"num_experts": 2, "top_k": 1, "dropless": True,
                              "frequency": 2},
                      "optim": {"lr": 1e-3}},
            "precision": {"type": "mixed_precision"},
        })
        t = Trainer.from_config(cfg, enable_checkpointing=False)
        m = t.fit()
        assert np.isfinite(m["loss"])


class TestBlockTypes:
    """transformer_block_type layouts (reference transformer.py:1468-2084)
    and tokentype embeddings (language_model.py:194-328) — VERDICT r2 item 9."""

    @pytest.mark.parametrize("bt", ["pre_ln", "post_ln", "normformer", "gpt_j"])
    def test_forward_and_grads_finite(self, bt):
        cfg = gpt.GPTConfig(**{**BASE, "num_layers": 1,
                               "transformer_block_type": bt})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1), b=1, s=8)
        loss, grads = jax.value_and_grad(
            lambda p: gpt.forward(p, batch, cfg, FP32)[0]
        )(params)
        assert np.isfinite(float(loss))
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5
        gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
        assert np.isfinite(gn) and gn > 0

    def test_layouts_differ_from_pre_ln(self):
        batch = _batch(jax.random.PRNGKey(1))
        outs = {}
        for bt in ("pre_ln", "post_ln", "gpt_j"):
            cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": bt})
            # same seed: pre_ln/post_ln share the same param structure
            params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
            logits, _ = gpt.forward(
                params, {"input_ids": batch["input_ids"]}, cfg, FP32)
            outs[bt] = np.asarray(logits)
        assert not np.allclose(outs["pre_ln"], outs["post_ln"])
        assert not np.allclose(outs["pre_ln"], outs["gpt_j"])

    def test_gpt_j_matches_manual_parallel_residual(self):
        """1-layer gpt_j equals the hand-computed parallel residual: attn on
        input_norm(x), MLP on post_attn_norm(x) — TWO independent norms
        (reference transformer.py:1908-1914)."""
        cfg = gpt.GPTConfig(**{**BASE, "num_layers": 1,
                               "transformer_block_type": "gpt_j"})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        ids = _batch(jax.random.PRNGKey(1))["input_ids"]
        logits, _ = gpt.forward(params, {"input_ids": ids}, cfg, FP32)

        lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
        from neuronx_distributed_training_tpu.ops import linear as linear_ops
        x = linear_ops.apply_embedding(params["embed"], ids,
                                       compute_dtype=FP32.compute_dtype)
        cos, sin = gpt._rope_for(cfg, ids)
        attn_out = gpt._attention_block(
            cfg, lp["attn"], gpt._apply_norm(cfg, lp["input_norm"], x),
            cos, sin, FP32)
        mlp_out, _ = gpt._mlp_block(
            cfg, lp["mlp"], gpt._apply_norm(cfg, lp["post_attn_norm"], x), FP32)
        y = x + attn_out + mlp_out
        hidden = gpt._apply_norm(cfg, params["final_norm"], y)
        ref = gpt._logits_from_hidden(params, hidden, cfg, FP32)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_normformer_has_extra_norms(self):
        cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": "normformer"})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        assert "nf_attn_norm" in params["layers"]
        assert "nf_mlp_norm" in params["layers"]
        assert params["layers"]["nf_mlp_norm"]["scale"].shape[-1] == cfg.ffn_size
        # specs cover every param leaf
        specs = gpt.param_specs(cfg)
        jax.tree_util.tree_map(lambda p, s: None, params, specs,
                               is_leaf=lambda x: isinstance(x, P))

    def test_gpt_j_keeps_two_norms(self):
        # the reference gpt_j layout norms attn and MLP with two SEPARATE
        # parameter sets (transformer.py:1908-1914)
        cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": "gpt_j"})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        assert "post_attn_norm" in params["layers"]
        assert "input_norm" in params["layers"]

    def test_post_ln_has_no_final_norm(self):
        # the reference builds no final layernorm for post_ln
        # (transformer.py:2478, 2569-2570)
        cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": "post_ln"})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        assert "final_norm" not in params
        specs = gpt.param_specs(cfg)
        assert "final_norm" not in specs

    def test_unknown_block_type_raises(self):
        cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": "sandwich"})
        with pytest.raises(ValueError, match="transformer_block_type"):
            gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)

    def test_normformer_moe_rejected(self):
        cfg = gpt.GPTConfig(**{**BASE, "transformer_block_type": "normformer"},
                            moe=moe_ops.MoEConfig(num_experts=2, top_k=1,
                                                  dropless=True))
        with pytest.raises(ValueError, match="dense-only"):
            gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)


class TestTokentype:
    def test_tokentype_changes_logits_and_matches_manual(self):
        cfg = gpt.GPTConfig(**{**BASE, "num_tokentypes": 2})
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        ids = _batch(jax.random.PRNGKey(1))["input_ids"]
        tt = jnp.zeros_like(ids).at[:, 8:].set(1)
        base_logits, _ = gpt.forward(params, {"input_ids": ids}, cfg, FP32)
        tt_logits, _ = gpt.forward(
            params, {"input_ids": ids, "tokentype_ids": tt}, cfg, FP32)
        assert not np.allclose(np.asarray(base_logits), np.asarray(tt_logits))
        # all-zero tokentypes = adding row 0 everywhere, NOT a no-op
        z_logits, _ = gpt.forward(
            params, {"input_ids": ids, "tokentype_ids": jnp.zeros_like(ids)},
            cfg, FP32)
        assert not np.allclose(np.asarray(base_logits), np.asarray(z_logits))

    def test_tokentype_ids_without_table_raises(self):
        cfg = gpt.GPTConfig(**BASE)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        ids = _batch(jax.random.PRNGKey(1))["input_ids"]
        with pytest.raises(ValueError, match="num_tokentypes"):
            gpt.forward(params, {"input_ids": ids,
                                 "tokentype_ids": jnp.zeros_like(ids)},
                        cfg, FP32)

    def test_from_config_reads_block_type_and_tokentypes(self):
        cfg = gpt.GPTConfig.from_config(
            {"transformer_block_type": "post_ln", "num_tokentypes": 3,
             "hidden_size": 32, "num_layers": 2, "num_attention_heads": 4},
        )
        assert cfg.transformer_block_type == "post_ln"
        assert cfg.num_tokentypes == 3
