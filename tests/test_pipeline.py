"""Pipeline parallelism: pp>1 loss/grads must match the unpipelined numerics."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.parallel.pipeline import pipeline_loss, stage_layer_slice
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

import pytest as _pytest_mark

pytestmark = _pytest_mark.mark.slow  # multi-minute parity tests; CI fast tier deselects

FP32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                   softmax_dtype=jnp.float32)

CFG = llama.LlamaConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_layers=4,
    num_attention_heads=4,
    num_kv_heads=2,
    max_position_embeddings=32,
    activations_checkpoint_granularity=None,
)


def microbatches(key, nm=4, mb=4, s=16):
    ids = jax.random.randint(key, (nm, mb, s), 0, CFG.vocab_size)
    return {"input_ids": ids, "labels": ids}


def flat_batch(mbs):
    return {k: v.reshape((-1,) + v.shape[2:]) for k, v in mbs.items()}


def ref_loss(params, mbs):
    return llama.forward(params, flat_batch(mbs), CFG, FP32)[0]


def pipe_loss(params, mbs, mesh):
    embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(CFG, FP32)
    return pipeline_loss(
        params, params["layers"], mbs,
        embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
    )



def shard_inputs(mesh, params, mbs):
    """device_put params (pipeline specs) + microbatches onto ``mesh``."""
    specs = llama.param_specs(CFG, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    sh_mbs = jax.device_put(mbs, ns(P(None, ("data", "expert"))))
    return sh_params, sh_mbs


def assert_grads_close(grads, ref_grads, paths, tag=""):
    for path in paths:
        g, rg = grads, ref_grads
        for k in path:
            g, rg = g[k], rg[k]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path} {tag}",
        )


class TestPipelineParity:
    def test_stage_layer_slice(self):
        assert stage_layer_slice(8, 2) == 4
        with pytest.raises(ValueError):
            stage_layer_slice(5, 2)

    @pytest.mark.parametrize("pp,tp", [(2, 1), (4, 1), (2, 2)])
    def test_loss_and_grads_match_unpipelined(self, devices8, pp, tp):
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))

        ref, ref_grads = jax.value_and_grad(ref_loss)(params, mbs)

        mesh = build_mesh(MeshConfig(
            pipeline_model_parallel_size=pp, tensor_model_parallel_size=tp))
        sh_params, sh_mbs = shard_inputs(mesh, params, mbs)
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(
                jax.value_and_grad(lambda p, m: pipe_loss(p, m, mesh), argnums=0)
            )(sh_params, sh_mbs)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        assert_grads_close(grads, ref_grads, (
            ("embed", "embedding"),
            ("final_norm", "scale"),
            ("layers", "mlp", "down", "w"),
            ("layers", "attn", "qkv", "w"),
        ))

    def test_nm_not_divisible_by_pp(self, devices8):
        """nm % pp != 0: the round-robin parking/embed layout pads to
        ceil(nm/pp) slots per rank; padded rows must not leak into loss or
        grads (r4 design, reviewed-but-untested path)."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=6)  # pp=4 -> slots=2, 2 pads

        ref, ref_grads = jax.value_and_grad(ref_loss)(params, mbs)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=4))
        sh_params, sh_mbs = shard_inputs(mesh, params, mbs)
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(
                jax.value_and_grad(lambda p, m: pipe_loss(p, m, mesh), argnums=0)
            )(sh_params, sh_mbs)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        assert_grads_close(
            grads, ref_grads,
            (("embed", "embedding"), ("layers", "mlp", "down", "w")),
            tag="(nm=6, pp=4)",
        )

    def test_forward_collective_budget(self, devices8):
        """Regression guard on the wavefront's comm schedule: the FORWARD
        pipeline at pp=4/tp=1 compiles exactly 2*pp+1 collective-permutes
        (the ring hop, plus one instruction per switch branch for the
        tick-uniform embed route and parked route).  On new jax
        (partial-auto shard_map) NO all-gather is permitted at all; on the
        legacy fully-manual fallback exactly one is — the in-spec
        re-replication of the pipe-sharded embed feed over the auto axes,
        an inherent (documented) cost of that fallback, not a schedule
        regression."""
        from neuronx_distributed_training_tpu.utils.debug import collective_counts

        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=4))
        sh_params, sh_mbs = shard_inputs(mesh, params, mbs)
        with mesh, shd.use_mesh(mesh):
            f = jax.jit(lambda p, m: pipe_loss(p, m, mesh))
            counts = collective_counts(f, sh_params, sh_mbs)
        assert counts["collective-permute"] == 2 * 4 + 1, counts
        gather_budget = 0 if hasattr(jax, "shard_map") else 1
        assert counts["all-gather"] <= gather_budget, counts

    def test_pp1_fallback_matches(self):
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))
        ref = ref_loss(params, mbs)
        loss = pipe_loss(params, mbs, None)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)

    def test_loss_mask_weighting(self, devices8):
        """Masked tokens must drop out of the pipelined global mean exactly."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))
        mask = np.ones(mbs["input_ids"].shape, np.float32)
        mask[0, :, :8] = 0.0  # mask half of microbatch 0
        mbs["loss_mask"] = jnp.asarray(mask)

        ref = ref_loss(params, mbs)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        specs = llama.param_specs(CFG, pipeline=True)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        with mesh, shd.use_mesh(mesh):
            loss = jax.jit(lambda p, m: pipe_loss(p, m, mesh))(sh_params, mbs)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)


class TestVirtualPipeline:
    @pytest.mark.parametrize("pp,vp", [(2, 2), (4, 2)])
    def test_vpp_matches_unpipelined(self, devices8, pp, vp):
        """Interleaved schedule (vp chunks per rank) must match plain numerics."""
        import dataclasses

        from neuronx_distributed_training_tpu.parallel.pipeline import to_interleaved

        cfg = dataclasses.replace(CFG, num_layers=pp * vp)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))

        def ref_loss_local(p, m):
            return llama.forward(p, flat_batch(m), cfg, FP32)[0]

        ref, ref_grads = jax.value_and_grad(ref_loss_local)(params, mbs)

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=pp))
        embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, FP32)

        def vpp_loss(p, m):
            inter = to_interleaved(p["layers"], pp, vp)
            return pipeline_loss(
                p, inter, m, embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
                mesh=mesh, virtual_pipeline_size=vp,
            )

        ns = functools.partial(NamedSharding, mesh)
        # layers replicated here ([L] stacked); the interleave happens in-jit.
        sh_params = jax.device_put(params, ns(P()))
        sh_mbs = jax.device_put(mbs, ns(P(None, ("data", "expert"))))
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(vpp_loss, argnums=0))(
                sh_params, sh_mbs
            )
        np.testing.assert_allclose(float(loss), float(ref), rtol=2e-5)
        for path in (("embed", "embedding"), ("layers", "attn", "qkv", "w")):
            g, rg = grads, ref_grads
            for k in path:
                g, rg = g[k], rg[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
                err_msg=f"grad mismatch at {path}",
            )

    @pytest.mark.parametrize("ep", [1, 2])
    def test_mixtral_pp2_matches_per_microbatch_reference(self, devices8, ep):
        """Mixtral under pp=2: lm loss + psum'd router aux must equal the mean
        of per-microbatch unpipelined forwards (routing is per-microbatch, so
        that — not the flat-batch forward — is the exact reference).  With
        ep 2 the stage body's expert block sends its rows over ``expert``."""
        import dataclasses

        from neuronx_distributed_training_tpu.models import mixtral
        from neuronx_distributed_training_tpu.ops import moe as moe_ops

        cfg = mixtral.MixtralConfig(
            llama=dataclasses.replace(CFG, num_layers=4),
            moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                                  router_aux_loss_coef=0.02),
        )
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))
        nm = mbs["input_ids"].shape[0]

        def ref(p, m):
            def body(acc, mb):
                loss, _ = mixtral.forward(p, mb, cfg, FP32)
                return acc + loss, None

            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
            return total / nm

        ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2,
                                     expert_model_parallel_size=ep))
        embed_fn, stage_fn, loss_fn = mixtral.pipeline_hooks(cfg, FP32)

        def pl(p, m):
            return pipeline_loss(
                p, p["layers"], m,
                embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
                mesh=mesh, stage_aux=True,
                aux_scale=1.0 / (nm * cfg.num_layers),
            )

        specs = mixtral.param_specs(cfg, pipeline=True)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
            loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
        # inside the pipe-manual stage body the dropless expert block nests
        # its own region over the axes that shard the micro-batch (data 4, or
        # data 2 x expert 2: one row a chip, 16 x 2 expert rows its fair share,
        # twice that all it can receive)
        assert traced == {"moe_token_shards": 4, **({} if ep == 1 else {
            "moe_expert_exchange": "tokens", "moe_row_bounds": [64]})}
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
        for path in (
            ("layers", "mlp", "router", "w"),
            ("layers", "mlp", "experts", "gate_up"),
            ("layers", "mlp", "experts", "down"),
            ("embed", "embedding"),
        ):
            g, rg = grads, ref_g
            for k in path:
                g, rg = g[k], rg[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
                err_msg=f"grad mismatch at {path}",
            )

    def test_gpt_pp2_matches_unpipelined(self, devices8):
        """Megatron GPT (learned-abs pos, layernorm+bias, gelu, tied head)
        under pp=2 matches the flat-batch forward."""
        from neuronx_distributed_training_tpu.models import gpt

        cfg = gpt.GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=4, num_attention_heads=4,
            max_position_embeddings=32, position_embedding_type="learned_absolute",
            activations_checkpoint_granularity=None,
        )
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))

        def ref(p, m):
            return gpt.forward(p, flat_batch(m), cfg, FP32)[0]

        ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        embed_fn, stage_fn, loss_fn = gpt.pipeline_hooks(cfg, FP32)

        def pl(p, m):
            return pipeline_loss(
                p, p["layers"], m,
                embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
                mesh=mesh, stage_aux=True, aux_scale=0.0,
            )

        specs = gpt.param_specs(cfg, pipeline=True)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
        for path in (("embed", "embedding"), ("layers", "attn", "qkv", "w"),
                     ("pos_embed", "embedding")):
            g, rg = grads, ref_g
            for k in path:
                g, rg = g[k], rg[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
                err_msg=f"grad mismatch at {path}",
            )

    def test_gpt_pp2_dropout_runs(self, devices8):
        """Dropout under pp: per-microbatch _rng keys thread through stages."""
        from neuronx_distributed_training_tpu.models import gpt

        cfg = gpt.GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=4, num_attention_heads=4,
            max_position_embeddings=32, hidden_dropout=0.1, embedding_dropout=0.1,
            activations_checkpoint_granularity=None,
        )
        params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = dict(microbatches(jax.random.PRNGKey(1)))
        mbs["_rng"] = jax.random.split(jax.random.PRNGKey(7), 4)

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        embed_fn, stage_fn, loss_fn = gpt.pipeline_hooks(cfg, FP32)

        def pl(p, m):
            return pipeline_loss(
                p, p["layers"], m,
                embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
                mesh=mesh, stage_aux=True,
            )

        specs = gpt.param_specs(cfg, pipeline=True)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
        assert np.isfinite(float(loss))
        assert np.all(np.isfinite(np.asarray(grads["layers"]["attn"]["qkv"]["w"])))


class TestPreferencePipeline:
    """DPO/ORPO under pp via the concatenated forward (reference base_dpo.py:68-88)."""

    def _pref_mbs(self, key, nm=2, mb=4, s=16):
        kc, kr = jax.random.split(key)
        return {
            "chosen_input_ids": jax.random.randint(kc, (nm, mb, s), 0, CFG.vocab_size),
            "rejected_input_ids": jax.random.randint(kr, (nm, mb, s), 0, CFG.vocab_size),
        }

    @pytest.mark.parametrize("mode", ["dpo", "orpo"])
    def test_pp2_matches_direct_loss(self, devices8, mode):
        from neuronx_distributed_training_tpu.alignment.dpo import (
            make_dpo_loss_fn,
            preference_pipeline_hooks,
        )
        from neuronx_distributed_training_tpu.alignment.orpo import make_orpo_loss_fn
        from neuronx_distributed_training_tpu.ops import norm as norm_ops

        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = self._pref_mbs(jax.random.PRNGKey(1))
        nm = mbs["chosen_input_ids"].shape[0]
        if mode == "dpo":
            mbs["reference_chosen_logps"] = -5.0 * jnp.ones((nm, 4))
            mbs["reference_rejected_logps"] = -6.0 * jnp.ones((nm, 4))

        def fwd(p, batch):
            return llama.forward(p, batch, CFG, FP32)[0]  # no labels -> logits

        direct = (make_dpo_loss_fn(fwd, beta=0.1) if mode == "dpo"
                  else make_orpo_loss_fn(fwd, beta=0.1))

        def ref(p, m):
            def body(acc, mb):
                loss, _ = direct(p, mb, None)
                return acc + loss, None

            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
            return total / nm

        ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        base_embed, base_stage, _ = llama.pipeline_hooks(CFG, FP32)

        def head_fn(p, y):
            h = norm_ops.apply_rms_norm(p["final_norm"], y, eps=CFG.rms_norm_eps)
            return llama.logits_fn(p, h, CFG, FP32)

        embed_fn, stage_fn, loss_fn = preference_pipeline_hooks(
            base_embed, base_stage, head_fn, mode=mode, beta=0.1
        )

        def pl(p, m):
            return pipeline_loss(
                p, p["layers"], m,
                embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
            )

        specs = llama.param_specs(CFG, pipeline=True)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        with mesh, shd.use_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
        for path in (("embed", "embedding"), ("layers", "attn", "qkv", "w")):
            g, rg = grads, ref_g
            for k in path:
                g, rg = g[k], rg[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
                err_msg=f"grad mismatch at {path}",
            )

    def test_interleave_round_trip(self):
        from neuronx_distributed_training_tpu.parallel.pipeline import (
            from_interleaved,
            to_interleaved,
        )

        x = {"w": jnp.arange(24.0).reshape(8, 3)}
        inter = to_interleaved(x, pp=2, vp=2)
        assert inter["w"].shape == (2, 2, 2, 3)
        # stage s = c*pp + r covers layers [s*Lc, (s+1)*Lc)
        np.testing.assert_array_equal(
            np.asarray(inter["w"][1, 0]), np.asarray(x["w"][4:6])  # chunk1 rank0 = stage2
        )
        back = from_interleaved(inter)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x["w"]))


def test_mixtral_interleaved_pp2_matches_reference(devices8):
    """moe_frequency=2 under pp=2: grouped stage slicing (whole MoE+dense
    groups per rank) matches the per-microbatch unpipelined forward."""
    import dataclasses

    from neuronx_distributed_training_tpu.models import mixtral
    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    cfg = mixtral.MixtralConfig(
        llama=dataclasses.replace(CFG, num_layers=8),
        moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                              router_aux_loss_coef=0.02),
        moe_frequency=2,
    )
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
    mbs = microbatches(jax.random.PRNGKey(1))
    nm = mbs["input_ids"].shape[0]

    def ref(p, m):
        def body(acc, mb):
            loss, _ = mixtral.forward(p, mb, cfg, FP32)
            return acc + loss, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
        return total / nm

    ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
    embed_fn, stage_fn, loss_fn = mixtral.pipeline_hooks(cfg, FP32)

    def pl(p, m):
        return pipeline_loss(
            p, p["layers"], m,
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
            mesh=mesh, stage_aux=True,
            aux_scale=1.0 / (nm * mixtral.num_moe_layers(cfg)),
        )

    specs = mixtral.param_specs(cfg, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    with mesh, shd.use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
    for path in (("layers", "mlp", "moe", "router", "w"),
                 ("layers", "mlp", "dense", "gate_up", "w"),
                 ("embed", "embedding")):
        g, rg = grads, ref_g
        for k in path:
            g, rg = g[k], rg[k]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_chunked_ce_pp2_matches(devices8):
    """fusions.chunked_ce in the PP loss hook: numerics identical to the
    standard logits path."""
    import dataclasses

    params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
    mbs = microbatches(jax.random.PRNGKey(1))
    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
    specs = llama.param_specs(CFG, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )

    def pl(cfg):
        embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, FP32)

        def f(p, m):
            return pipeline_loss(
                p, p["layers"], m,
                embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
            )

        return f

    with mesh, shd.use_mesh(mesh):
        ref, ref_g = jax.jit(jax.value_and_grad(pl(CFG)))(sh_params, mbs)
        cfg2 = dataclasses.replace(CFG, vocab_chunks=4)
        got, got_g = jax.jit(jax.value_and_grad(pl(cfg2)))(sh_params, mbs)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got_g["embed"]["embedding"]),
        np.asarray(ref_g["embed"]["embedding"]), rtol=5e-4, atol=1e-6)


def test_gpt_interleaved_pp2_matches_reference(devices8):
    """GPT moe_frequency=2 under pp=2: grouped stage slicing (whole MoE+dense
    groups per rank) matches the per-microbatch unpipelined forward — the GPT
    mirror of the mixtral interleave test."""
    from neuronx_distributed_training_tpu.models import gpt
    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    cfg = gpt.GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=8, num_attention_heads=4,
        max_position_embeddings=32, normalization="rmsnorm", bias=False,
        activation="swiglu", ffn_hidden_size=64,
        activations_checkpoint_granularity=None,
        moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                              router_aux_loss_coef=0.02),
        moe_frequency=2,
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
    mbs = microbatches(jax.random.PRNGKey(1))
    nm = mbs["input_ids"].shape[0]

    def ref(p, m):
        def body(acc, mb):
            loss, _ = gpt.forward(p, mb, cfg, FP32)
            return acc + loss, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
        return total / nm

    ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
    embed_fn, stage_fn, loss_fn = gpt.pipeline_hooks(cfg, FP32)

    def pl(p, m):
        return pipeline_loss(
            p, p["layers"], m,
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
            mesh=mesh, stage_aux=True,
            aux_scale=1.0 / (nm * gpt.num_moe_layers(cfg)),
        )

    specs = gpt.param_specs(cfg, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    with mesh, shd.use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
    for path in (("layers", "mlp", "moe", "router", "w"),
                 ("layers", "mlp", "dense", "up", "w"),
                 ("layers", "attn", "qkv", "w"),
                 ("embed", "embedding")):
        g, rg = grads, ref_g
        for k in path:
            g, rg = g[k], rg[k]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )


def test_gpt_interleaved_pp2_dropout_runs(devices8):
    """Grouped dropout-key threading ([g, f] per stage) under pp=2."""
    from neuronx_distributed_training_tpu.models import gpt
    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    cfg = gpt.GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=8, num_attention_heads=4,
        max_position_embeddings=32, hidden_dropout=0.1,
        activations_checkpoint_granularity=None,
        moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True),
        moe_frequency=2,
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg, FP32)
    mbs = dict(microbatches(jax.random.PRNGKey(1)))
    mbs["_rng"] = jax.random.split(jax.random.PRNGKey(7), 4)

    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
    embed_fn, stage_fn, loss_fn = gpt.pipeline_hooks(cfg, FP32)

    def pl(p, m):
        return pipeline_loss(
            p, p["layers"], m,
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
            mesh=mesh, stage_aux=True,
            aux_scale=1.0 / (4 * gpt.num_moe_layers(cfg)),
        )

    specs = gpt.param_specs(cfg, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    with mesh, shd.use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
    assert np.isfinite(float(loss))
    assert np.all(np.isfinite(np.asarray(grads["layers"]["mlp"]["moe"]["router"]["w"])))


def test_mixtral_interleaved_vpp_matches_reference(devices8):
    """moe_frequency=2 under pp=2 x vp=2: grouped leaves ([G]-leading moe,
    [G, f-1] dense) reshape through to_interleaved consistently with the flat
    [L] attn/norm leaves (chunk layers = Gc*f)."""
    import dataclasses

    from neuronx_distributed_training_tpu.models import mixtral
    from neuronx_distributed_training_tpu.ops import moe as moe_ops
    from neuronx_distributed_training_tpu.parallel.pipeline import to_interleaved

    cfg = mixtral.MixtralConfig(
        llama=dataclasses.replace(CFG, num_layers=8),
        moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                              router_aux_loss_coef=0.02),
        moe_frequency=2,
    )
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
    mbs = microbatches(jax.random.PRNGKey(1))
    nm = mbs["input_ids"].shape[0]

    def ref(p, m):
        def body(acc, mb):
            loss, _ = mixtral.forward(p, mb, cfg, FP32)
            return acc + loss, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
        return total / nm

    ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

    pp, vp = 2, 2
    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=pp))
    embed_fn, stage_fn, loss_fn = mixtral.pipeline_hooks(cfg, FP32)
    inter = to_interleaved(params["layers"], pp, vp)
    p_inter = {**params, "layers": inter}

    def pl(p, m):
        return pipeline_loss(
            p, p["layers"], m,
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
            mesh=mesh, virtual_pipeline_size=vp, stage_aux=True,
            aux_scale=1.0 / (nm * mixtral.num_moe_layers(cfg)),
        )

    specs = mixtral.param_specs(cfg, pipeline=True)
    specs["layers"] = jax.tree_util.tree_map(
        lambda s: P(None, s[0], None, *tuple(s)[1:]), specs["layers"],
        is_leaf=lambda x: isinstance(x, P),
    )
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        p_inter, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    with mesh, shd.use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, mbs)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
    # grads come back in the interleaved layout; compare via to_interleaved(ref)
    ref_inter = to_interleaved(
        jax.tree_util.tree_map(np.asarray, ref_g["layers"]), pp, vp)
    for path in (("mlp", "moe", "router", "w"),
                 ("mlp", "dense", "gate_up", "w"),
                 ("attn", "qkv", "w")):
        g, rg = grads["layers"], ref_inter
        for k in path:
            g, rg = g[k], rg[k]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )
    np.testing.assert_allclose(
        np.asarray(grads["embed"]["embedding"]),
        np.asarray(ref_g["embed"]["embedding"]), rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_cp_attention_under_pp_matches(devices8, impl):
    """CP attention under pipeline parallelism (the reference's 70B CP
    flagship layout, hf_llama3_70B_CP_config: TP=32 PP=8 CP=2).  Inside the
    pipe-Manual pipeline body a nested shard_map corrupts backward for
    pipe-varying inputs, so ring/ulysses route to the GSPMD blockwise body —
    loss AND grads must match the unsharded core-attention reference."""
    import dataclasses

    cfg = dataclasses.replace(
        CFG, num_layers=2, attention_impl=impl, context_parallel=True,
        max_position_embeddings=64,
    )
    ref_cfg = dataclasses.replace(CFG, num_layers=2, max_position_embeddings=64)
    params = llama.init_params(jax.random.PRNGKey(0), ref_cfg, FP32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 64), 0, CFG.vocab_size)
    mbs = {"input_ids": ids, "labels": ids}
    nm = ids.shape[0]

    def ref(p, m):
        def body(acc, mb):
            return acc + llama.forward(p, mb, ref_cfg, FP32)[0], None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), m)
        return total / nm

    ref_l, ref_g = jax.value_and_grad(ref)(params, mbs)

    mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2,
                                 context_parallel_size=2,
                                 tensor_model_parallel_size=2))
    embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, FP32)

    def pl(p, m):
        return pipeline_loss(
            p, p["layers"], m,
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
        )

    specs = llama.param_specs(cfg, pipeline=True)
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    sh_mbs = jax.device_put(mbs, ns(P(None, ("data", "expert"), "context")))
    with mesh, shd.use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pl, argnums=0))(sh_params, sh_mbs)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
    for path in (("embed", "embedding"), ("layers", "attn", "qkv", "w")):
        g, rg = grads, ref_g
        for k in path:
            g, rg = g[k], rg[k]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(rg), rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {path}",
        )
