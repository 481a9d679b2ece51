"""1F1B pipeline schedule: gate, parity, return contract, memory bound.

The manual-vjp 1F1B (``parallel.pipeline.pipeline_loss_and_grad``) is the
production PP path whenever ``supports_1f1b`` allows.  Manual-vjp schedules
are exactly the code class that silently diverges from autodiff, so this file
runs FAST (not ``slow``): loss/grad parity against the autodiff wavefront is
exercised on every tier-1 run on the 8-device CPU mesh.

The memory test pins the schedule's reason to exist: compiled peak temp
memory of the 1F1B step grows sub-linearly in num_microbatches (only the
pre-computed embed feed and its cotangent scale with nm, ~1 activation per
microbatch per pipe rank), while the autodiff wavefront retains ~2
activation-sized residuals per microbatch (the per-tick stage-input saves
plus the parked/head chain) — the O(pp) vs O(nm + pp) divide at scale.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.parallel.pipeline import (
    MANUAL_VJP_SCHEDULES,
    PIPELINE_SCHEDULES,
    bubble_multiplier,
    pipeline_loss,
    pipeline_loss_and_grad,
    predicted_bubble_fraction,
    resolve_schedule,
    ring_slot_counts,
    supports_1f1b,
    to_interleaved,
    work_table,
)
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

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

GRAD_PATHS = (
    ("layers", "mlp", "down", "w"),
    ("layers", "attn", "qkv", "w"),
    ("layers", "input_norm", "scale"),
)


def _says(model_cfg):
    """The model's half of the gate: its family's answer (None: it can)."""
    return model_cfg.family.manual_vjp_refusal(model_cfg)


def _pcfg(pp=2, vp=1, alignment=None, lora=False):
    return {
        "pipeline_model_parallel_size": pp,
        "virtual_pipeline_model_parallel_size": vp,
        "alignment": alignment,
        "lora": lora,
    }


def microbatches(key, nm=4, mb=4, s=16, vocab=128):
    ids = jax.random.randint(key, (nm, mb, s), 0, vocab)
    return {"input_ids": ids, "labels": ids}


def shard_for(mesh, cfg, params, mbs, specs=None, vp=1):
    specs = specs if specs is not None else llama.param_specs(cfg, pipeline=True)
    if vp > 1:
        pp = int(mesh.shape.get("pipe", 1))
        params = {**params, "layers": to_interleaved(params["layers"], pp, vp)}
        specs = dict(specs)
        specs["layers"] = jax.tree_util.tree_map(
            lambda s: P(None, s[0], None, *tuple(s)[1:]), specs["layers"],
            is_leaf=lambda x: isinstance(x, P))
    ns = functools.partial(NamedSharding, mesh)
    sh_params = jax.device_put(
        params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
    )
    sh_mbs = jax.device_put(mbs, ns(P(None, ("data", "expert"))))
    return sh_params, sh_mbs


def wavefront_loss_and_grad(mesh, hooks, params, mbs, **kw):
    embed_fn, stage_fn, loss_fn = hooks

    def wf(p, m):
        return pipeline_loss(
            p, p["layers"], m, embed_fn=embed_fn, stage_fn=stage_fn,
            loss_fn=loss_fn, mesh=mesh, **kw,
        )

    with mesh, shd.use_mesh(mesh):
        return jax.jit(jax.value_and_grad(wf))(params, mbs)


def onef1b_loss_and_grad(mesh, cfg, hooks, params, mbs, **kw):
    embed_fn, stage_fn, _ = hooks
    hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, FP32)

    def f1b(p, m):
        return pipeline_loss_and_grad(
            p, p["layers"], m, embed_fn=embed_fn, stage_fn=stage_fn,
            head_hidden_fn=hh, head_params=hp_of(p), head_weight=hw_of(p),
            mesh=mesh, **kw,
        )

    with mesh, shd.use_mesh(mesh):
        return jax.jit(f1b)(params, mbs)


def assert_path_close(got, want, paths, rtol=5e-4, atol=1e-5, tag=""):
    for path in paths:
        a, b = got, want
        for k in path:
            a, b = a[k], b[k]
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol,
            err_msg=f"grad mismatch at {path} {tag}",
        )


class TestSupports1F1B:
    """The schedule gate, combination by combination."""

    def test_llama_pp2_supported(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2))
        assert ok, reason

    def test_pp1_unsupported(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=1))
        assert not ok and "pipeline_model_parallel_size" in reason

    def test_plain_1f1b_rejects_vp_naming_interleaved(self):
        """The vp>1 message points at the interleaved schedule now — not at
        the autodiff wavefront (satellite: stale-message fix)."""
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2, vp=2))
        assert not ok and "1f1b-interleaved" in reason
        assert "wavefront" not in reason

    def test_interleaved_supported_with_vp(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2, vp=2),
                                   "1f1b-interleaved")
        assert ok, reason

    def test_interleaved_needs_vp(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2), "1f1b-interleaved")
        assert not ok and "nothing to interleave" in reason

    def test_zb_supported_at_vp1_only(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2), "1f1b-zb")
        assert ok, reason
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2, vp=2), "1f1b-zb")
        assert not ok and "1f1b-interleaved" in reason

    @pytest.mark.parametrize("sched", MANUAL_VJP_SCHEDULES)
    def test_cp_blocks_every_manual_vjp_schedule(self, sched):
        pcfg = dict(_pcfg(pp=2, vp=2 if sched == "1f1b-interleaved" else 1),
                    context_parallel_size=2)
        ok, reason = supports_1f1b(_says(CFG), pcfg, sched)
        assert not ok and "context" in reason

    def test_non_manual_schedule_rejected_by_gate(self):
        with pytest.raises(ValueError, match="manual-vjp"):
            supports_1f1b(_says(CFG), _pcfg(pp=2), "wavefront")

    def test_cp_unsupported(self):
        pcfg = dict(_pcfg(pp=2), context_parallel_size=2)
        ok, reason = supports_1f1b(_says(CFG), pcfg)
        assert not ok and "context" in reason
        assert resolve_schedule("auto", _says(CFG), pcfg) == "wavefront"

    @pytest.mark.parametrize("alignment", ["dpo", "orpo", "kto"])
    def test_preference_alignment_unsupported(self, alignment):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2, alignment=alignment))
        assert not ok and alignment in reason

    def test_sft_alignment_supported(self):
        ok, _ = supports_1f1b(_says(CFG), _pcfg(pp=2, alignment="sft"))
        assert ok

    def test_lora_unsupported(self):
        ok, reason = supports_1f1b(_says(CFG), _pcfg(pp=2, lora=True))
        assert not ok and "LoRA" in reason

    def test_gpt_unsupported(self):
        from neuronx_distributed_training_tpu.models import gpt

        gc = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                           num_attention_heads=4, max_position_embeddings=32)
        ok, reason = supports_1f1b(_says(gc), _pcfg(pp=2))
        assert not ok and "GPTConfig" in reason

    def test_mixtral_unsupported_keeps_wavefront(self):
        """Dropless-MoE stage vjp has backend-dependent numerics inside the
        1f1b tick loop (bisected: loss exact, stage grads off by a few
        percent under the legacy fully-manual shard_map fallback), so the
        gate keeps mixtral on the autodiff wavefront — and ``auto`` must
        resolve there rather than erroring."""
        import dataclasses

        from neuronx_distributed_training_tpu.models import mixtral
        from neuronx_distributed_training_tpu.ops import moe as moe_ops

        xc = mixtral.MixtralConfig(
            llama=dataclasses.replace(CFG),
            moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True),
        )
        ok, reason = supports_1f1b(_says(xc), _pcfg(pp=2))
        assert not ok and "mixtral" in reason
        assert resolve_schedule("auto", _says(xc), _pcfg(pp=2)) == "wavefront"
        with pytest.raises(ValueError, match="mixtral"):
            resolve_schedule("1f1b", _says(xc), _pcfg(pp=2))

    def test_zigzag_unsupported(self):
        import dataclasses

        zz = dataclasses.replace(CFG, attention_impl="zigzag_ring")
        ok, reason = supports_1f1b(_says(zz), _pcfg(pp=2))
        assert not ok and "zigzag" in reason


class TestResolveSchedule:
    def test_auto_picks_1f1b_when_supported(self):
        assert resolve_schedule("auto", _says(CFG), _pcfg(pp=2)) == "1f1b"

    def test_auto_picks_interleaved_under_vp(self):
        assert resolve_schedule("auto", _says(CFG), _pcfg(pp=2, vp=2)) \
            == "1f1b-interleaved"

    def test_auto_falls_back_to_wavefront(self):
        pcfg = dict(_pcfg(pp=2, vp=2), context_parallel_size=2)
        assert resolve_schedule("auto", _says(CFG), pcfg) == "wavefront"

    def test_auto_never_picks_zb(self):
        """zb trades recompute for bubble — a per-plan call the autotune
        cost model prices; auto stays on the no-extra-compute default."""
        assert resolve_schedule("auto", _says(CFG), _pcfg(pp=2)) == "1f1b"

    def test_forced_interleaved_and_zb(self):
        assert resolve_schedule("1f1b-interleaved", _says(CFG), _pcfg(pp=2, vp=2)) \
            == "1f1b-interleaved"
        assert resolve_schedule("1f1b-zb", _says(CFG), _pcfg(pp=2)) == "1f1b-zb"
        with pytest.raises(ValueError, match="nothing to interleave"):
            resolve_schedule("1f1b-interleaved", _says(CFG), _pcfg(pp=2))
        with pytest.raises(ValueError, match="1f1b-interleaved"):
            resolve_schedule("1f1b-zb", _says(CFG), _pcfg(pp=2, vp=2))

    def test_forced_wavefront_always_wins(self):
        assert resolve_schedule("wavefront", _says(CFG), _pcfg(pp=2)) == "wavefront"

    def test_forced_1f1b_on_supported(self):
        assert resolve_schedule("1f1b", _says(CFG), _pcfg(pp=2)) == "1f1b"

    def test_forced_1f1b_on_unsupported_raises_with_reason(self):
        with pytest.raises(ValueError, match="virtual"):
            resolve_schedule("1f1b", _says(CFG), _pcfg(pp=2, vp=2))
        with pytest.raises(ValueError, match="dpo"):
            resolve_schedule("1f1b", _says(CFG), _pcfg(pp=2, alignment="dpo"))

    def test_unknown_schedule_rejected(self):
        with pytest.raises(ValueError, match="pipeline.schedule"):
            resolve_schedule("gpipe", _says(CFG), _pcfg(pp=2))
        assert PIPELINE_SCHEDULES == ("auto", "1f1b", "1f1b-interleaved",
                                      "1f1b-zb", "wavefront")
        assert MANUAL_VJP_SCHEDULES == ("1f1b", "1f1b-interleaved", "1f1b-zb")

    def test_default_none_means_auto(self):
        assert resolve_schedule(None, _says(CFG), _pcfg(pp=2)) == "1f1b"


class TestParity:
    """1F1B loss and ALL grad families must match wavefront + jax.grad —
    the feature-defining test for a manual-vjp schedule."""

    @pytest.mark.parametrize("tied", [False, True])
    def test_pp2_loss_and_grads_match_wavefront(self, devices8, tied):
        import dataclasses

        cfg = dataclasses.replace(CFG, tie_word_embeddings=tied)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1))
        hooks = llama.pipeline_hooks(cfg, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, cfg, params, mbs)

        ref_l, ref_g = wavefront_loss_and_grad(mesh, hooks, sh_params, sh_mbs)
        loss, g = onef1b_loss_and_grad(mesh, cfg, hooks, sh_params, sh_mbs)

        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
        assert_path_close(g["layers"], ref_g["layers"],
                          tuple(p[1:] for p in GRAD_PATHS), tag=f"(tied={tied})")
        np.testing.assert_allclose(
            np.asarray(g["head_params"]["final_norm"]["scale"]),
            np.asarray(ref_g["final_norm"]["scale"]), rtol=5e-4, atol=1e-5)
        d_embed = np.asarray(g["params_from_embed"]["embed"]["embedding"])
        if tied:
            # tied head: embed grad = embed-path cotangent + head matmul grad
            np.testing.assert_allclose(
                d_embed + np.asarray(g["head_weight"]),
                np.asarray(ref_g["embed"]["embedding"]), rtol=5e-4, atol=1e-5)
        else:
            np.testing.assert_allclose(
                d_embed, np.asarray(ref_g["embed"]["embedding"]),
                rtol=5e-4, atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(g["head_weight"]).T,
                np.asarray(ref_g["lm_head"]["w"]), rtol=5e-4, atol=1e-5)

    def test_pp4_nm_not_divisible(self, devices8):
        """nm % pp != 0: padded embed-feed/cotangent slots must not leak."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=6)  # pp=4 -> 2 pad rows
        hooks = llama.pipeline_hooks(CFG, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=4))
        sh_params, sh_mbs = shard_for(mesh, CFG, params, mbs)

        ref_l, ref_g = wavefront_loss_and_grad(mesh, hooks, sh_params, sh_mbs)
        loss, g = onef1b_loss_and_grad(mesh, CFG, hooks, sh_params, sh_mbs)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g["params_from_embed"]["embed"]["embedding"]),
            np.asarray(ref_g["embed"]["embedding"]), rtol=5e-4, atol=1e-5,
            err_msg="(nm=6, pp=4)")
        assert_path_close(g["layers"], ref_g["layers"],
                          (("mlp", "down", "w"),), tag="(nm=6, pp=4)")

    def test_loss_mask_weighting(self, devices8):
        """Masked tokens drop out of loss AND denominator identically."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = dict(microbatches(jax.random.PRNGKey(1)))
        mask = np.ones(np.asarray(mbs["input_ids"]).shape, np.float32)
        mask[0, :, :8] = 0.0
        mbs["loss_mask"] = jnp.asarray(mask)
        hooks = llama.pipeline_hooks(CFG, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, CFG, params, mbs)

        ref_l, _ = wavefront_loss_and_grad(mesh, hooks, sh_params, sh_mbs)
        loss, _ = onef1b_loss_and_grad(mesh, CFG, hooks, sh_params, sh_mbs)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)

    def test_return_contract(self, devices8):
        """The documented grads contract is a tested invariant: exactly the
        keys {layers, params_from_embed, head_params, head_weight}, with
        params_from_embed shaped like the FULL params tree (vjp applied
        internally — not a raw embed-feed cotangent)."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=2)
        hooks = llama.pipeline_hooks(CFG, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, CFG, params, mbs)
        _, g = onef1b_loss_and_grad(mesh, CFG, hooks, sh_params, sh_mbs)
        assert sorted(g) == ["head_params", "head_weight", "layers",
                             "params_from_embed"]
        assert (jax.tree_util.tree_structure(g["params_from_embed"])
                == jax.tree_util.tree_structure(params))
        same_shapes = jax.tree_util.tree_map(
            lambda a, b: a.shape == b.shape, g["params_from_embed"], params)
        assert all(jax.tree_util.tree_leaves(same_shapes))
        # head grads cover the head param subtree, vocab-major head weight
        assert sorted(g["head_params"]) == ["final_norm"]
        V, H = CFG.vocab_size, CFG.hidden_size
        assert g["head_weight"].shape == (V, H)

    def test_pp1_raises(self):
        hooks = llama.pipeline_hooks(CFG, FP32)
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=2)
        embed_fn, stage_fn, _ = hooks
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(CFG, FP32)
        with pytest.raises(ValueError, match="pp > 1"):
            pipeline_loss_and_grad(
                params, params["layers"], mbs, embed_fn=embed_fn,
                stage_fn=stage_fn, head_hidden_fn=hh,
                head_params=hp_of(params), head_weight=hw_of(params),
                mesh=None)


class TestParityNewSchedules:
    """The circular interleaved 1F1B and the ZB-H1 split must hold the SAME
    parity bar as plain 1F1B: loss + all grad families vs wavefront +
    ``jax.grad`` at the pinned tolerances.  The wavefront reference runs with
    the identical vp (so both sides consume the identical interleaved layer
    layout and chunk schedule)."""

    def _compare(self, cfg, pp, vp, nm, *, zb=False, loss_mask=False,
                 tied=False):
        import dataclasses

        cfg = dataclasses.replace(cfg, tie_word_embeddings=tied)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = dict(microbatches(jax.random.PRNGKey(1), nm=nm,
                                vocab=cfg.vocab_size))
        if loss_mask:
            mask = np.ones(np.asarray(mbs["input_ids"]).shape, np.float32)
            mask[0, :, :8] = 0.0
            mbs["loss_mask"] = jnp.asarray(mask)
        hooks = llama.pipeline_hooks(cfg, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=pp,
                                     virtual_pipeline_model_parallel_size=vp))
        sh_params, sh_mbs = shard_for(mesh, cfg, params, mbs, vp=vp)

        ref_l, ref_g = wavefront_loss_and_grad(
            mesh, hooks, sh_params, sh_mbs, virtual_pipeline_size=vp)
        loss, g = onef1b_loss_and_grad(
            mesh, cfg, hooks, sh_params, sh_mbs,
            virtual_pipeline_size=vp, zero_bubble=zb)
        tag = f"(pp={pp}, vp={vp}, nm={nm}, zb={zb}, tied={tied})"
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5,
                                   err_msg=tag)
        assert_path_close(g["layers"], ref_g["layers"],
                          tuple(p[1:] for p in GRAD_PATHS), tag=tag)
        np.testing.assert_allclose(
            np.asarray(g["head_params"]["final_norm"]["scale"]),
            np.asarray(ref_g["final_norm"]["scale"]), rtol=5e-4, atol=1e-5,
            err_msg=tag)
        d_embed = np.asarray(g["params_from_embed"]["embed"]["embedding"])
        if tied:
            np.testing.assert_allclose(
                d_embed + np.asarray(g["head_weight"]),
                np.asarray(ref_g["embed"]["embedding"]), rtol=5e-4,
                atol=1e-5, err_msg=tag)
        else:
            np.testing.assert_allclose(
                d_embed, np.asarray(ref_g["embed"]["embedding"]),
                rtol=5e-4, atol=1e-5, err_msg=tag)
            np.testing.assert_allclose(
                np.asarray(g["head_weight"]).T,
                np.asarray(ref_g["lm_head"]["w"]), rtol=5e-4, atol=1e-5,
                err_msg=tag)

    @pytest.mark.parametrize("pp,nm,tied", [
        (2, 4, False), (2, 4, True), (2, 6, False), (4, 6, False),
    ])
    def test_interleaved_parity(self, devices8, pp, nm, tied):
        """vp=2 circular interleave at pp in {2, 4}, incl. nm % pp != 0 and
        tied embeddings.  pp=4 x vp=2 needs an 8-layer stack."""
        import dataclasses

        cfg = (dataclasses.replace(CFG, num_layers=8) if pp == 4 else CFG)
        self._compare(cfg, pp=pp, vp=2, nm=nm, tied=tied)

    @pytest.mark.parametrize("pp,nm,tied", [
        (2, 4, True), (2, 6, False), (4, 4, False), (4, 6, False),
    ])
    def test_zb_parity(self, devices8, pp, nm, tied):
        """ZB-H1 dgrad/wgrad split at pp in {2, 4}, incl. nm % pp != 0 and
        tied embeddings."""
        self._compare(CFG, pp=pp, vp=1, nm=nm, zb=True, tied=tied)

    def test_interleaved_loss_mask(self, devices8):
        self._compare(CFG, pp=2, vp=2, nm=4, loss_mask=True)

    def test_zb_loss_mask(self, devices8):
        self._compare(CFG, pp=2, vp=1, nm=4, zb=True, loss_mask=True)

    def test_zb_rejects_vp(self, devices8):
        hooks = llama.pipeline_hooks(CFG, FP32)
        embed_fn, stage_fn, _ = hooks
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(CFG, FP32)
        params = llama.init_params(jax.random.PRNGKey(0), CFG, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=4)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2,
                                     virtual_pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, CFG, params, mbs, vp=2)
        with pytest.raises(ValueError, match="vp == 1 only"):
            pipeline_loss_and_grad(
                sh_params, sh_params["layers"], sh_mbs, embed_fn=embed_fn,
                stage_fn=stage_fn, head_hidden_fn=hh,
                head_params=hp_of(sh_params), head_weight=hw_of(sh_params),
                mesh=mesh, virtual_pipeline_size=2, zero_bubble=True)

    def test_interleaved_needs_nm_ge_pp(self, devices8):
        """nm < pp would read the circular stores before their writes —
        must die loudly (same hazard rule the wavefront enforces)."""
        import dataclasses

        cfg = dataclasses.replace(CFG, num_layers=8)
        hooks = llama.pipeline_hooks(cfg, FP32)
        embed_fn, stage_fn, _ = hooks
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, FP32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=2)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=4,
                                     virtual_pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, cfg, params, mbs, vp=2)
        with pytest.raises(ValueError, match="num_microbatches >= pp"):
            pipeline_loss_and_grad(
                sh_params, sh_params["layers"], sh_mbs, embed_fn=embed_fn,
                stage_fn=stage_fn, head_hidden_fn=hh,
                head_params=hp_of(sh_params), head_weight=hw_of(sh_params),
                mesh=mesh, virtual_pipeline_size=2)


class TestBubbleModel:
    """The one bubble table telemetry and the autotune cost model
    share (``bubble_multiplier`` / ``predicted_bubble_fraction``)."""

    def test_classic_1f1b_and_wavefront(self):
        assert bubble_multiplier("1f1b", 4, 8) == pytest.approx(3 / 8)
        assert bubble_multiplier("wavefront", 4, 8) == pytest.approx(3 / 8)

    def test_wavefront_vp_divides(self):
        """The satellite fix: vp>1 wavefront utilization is
        nm*vp/(nm*vp + pp - 1), so the multiplier divides by nm*vp."""
        assert bubble_multiplier("wavefront", 4, 8, vp=2) \
            == pytest.approx(3 / 16)

    def test_interleaved_divides_by_nm_vp(self):
        assert bubble_multiplier("1f1b-interleaved", 4, 8, vp=2) \
            == pytest.approx(3 / 16)
        assert bubble_multiplier("1f1b-interleaved", 4, 8, vp=4) \
            == pytest.approx(3 / 32)

    def test_zb_is_the_warmup_third(self):
        assert bubble_multiplier("1f1b-zb", 4, 8) == pytest.approx(1 / 8)
        # strictly below plain 1f1b at every equal (pp, nm)
        for pp in (2, 4, 8):
            for nm in (4, 16, 64):
                assert bubble_multiplier("1f1b-zb", pp, nm) \
                    < bubble_multiplier("1f1b", pp, nm)

    def test_degenerate_cases(self):
        assert bubble_multiplier("1f1b", 1, 8) == 0.0
        assert bubble_multiplier(None, 4, 0) == 0.0
        assert predicted_bubble_fraction("none", 1, 8) == 0.0

    def test_fraction_is_of_total_step(self):
        b = bubble_multiplier("1f1b", 4, 8)
        assert predicted_bubble_fraction("1f1b", 4, 8) \
            == pytest.approx(b / (1 + b))
        # utilization identity: 1 - fraction == nm*vp/(nm*vp + pp - 1)
        assert 1 - predicted_bubble_fraction("wavefront", 4, 8, vp=2) \
            == pytest.approx(16 / 19)


class TestWorkTable:
    """The work-compacted schedule table (schedule as data): the executor's
    trip counts, orderings, and ring bounds are host-side facts that must
    hold by construction."""

    @pytest.mark.parametrize("sched,pp,nm,vp", [
        ("1f1b", 2, 4, 1), ("1f1b", 4, 8, 1), ("1f1b", 2, 16, 1),
        ("1f1b-interleaved", 2, 4, 2), ("1f1b-interleaved", 2, 16, 2),
        ("1f1b-interleaved", 4, 8, 2),
    ])
    def test_table_realizes_priced_bubble(self, sched, pp, nm, vp):
        """The compacted table's own bubble accounting equals the planner's
        closed-form b/(1+b) for 1f1b and the m-major interleave (nm % pp ==
        0): the executor realizes EXACTLY the priced asymptotics — the
        claim the old lockstep executor could not make."""
        b = bubble_multiplier(sched, pp, nm, vp)
        assert work_table(sched, pp, nm, vp).bubble_fraction() \
            == pytest.approx(b / (1 + b))
        assert predicted_bubble_fraction(sched, pp, nm, vp) \
            == pytest.approx(b / (1 + b))

    def test_compacted_span_below_lockstep(self):
        for sched, pp, nm, vp in [("1f1b", 2, 16, 1),
                                  ("1f1b-interleaved", 2, 16, 2),
                                  ("1f1b-zb", 2, 16, 1)]:
            t = work_table(sched, pp, nm, vp)
            assert t.span < t.lockstep_span, (sched, t.tick_counts())

    def test_dense_windows(self):
        """nm % pp == 0: the F and B windows are exactly nm*vp + pp - 1
        active ticks each — the compacted executor runs no more stage
        computations than the work demands plus the fill/drain triangles."""
        for sched, pp, nm, vp in [("1f1b", 2, 16, 1),
                                  ("1f1b-interleaved", 2, 16, 2)]:
            tc = work_table(sched, pp, nm, vp).tick_counts()
            assert tc["f_ticks"] == nm * vp + pp - 1
            assert tc["b_ticks"] == nm * vp + pp - 1
            assert tc["head_ticks"] == nm

    def test_interleave_ring_bound_beats_old_store(self):
        """The m-major interleave's interval-allocated rings are bounded by
        the schedule's true in-flight window — STRICTLY below the old
        lockstep store (vp*nm chunk inputs + two nm-slot hand-off rings)
        at the acceptance point pp=2/nm=16/vp=2, and independent of nm."""
        rings16 = ring_slot_counts("1f1b-interleaved", 2, 16, 2)
        assert rings16["total"] < 2 * 16  # old chunk-input store alone
        assert rings16["inflight"] < 2 * 16
        # nm-independence: the ring is a window, not a per-microbatch store
        rings32 = ring_slot_counts("1f1b-interleaved", 2, 32, 2)
        assert rings32["inflight"] == rings16["inflight"]
        assert rings32["total"] == rings16["total"]

    def test_zb_wgrad_fill_is_dense(self):
        """ZB's deferred wgrads land on rank-uniform fill ticks: every rank
        does a VALID wgrad on every wgrad tick (no masked wgrad burn)."""
        t = work_table("1f1b-zb", 4, 8, 1)
        w_valid = t.rank_cols["w_valid"]
        has_w = t.glob_cols["has_w"]
        assert int(has_w.sum()) == 8  # one dense tick per microbatch
        assert (w_valid[has_w].all(axis=1)).all()

    def test_slot_lifetimes_collision_free(self):
        """Re-derive every ring value's write->last-read lifetime from the
        table columns and assert no two values overlap in a slot."""
        for sched, pp, nm, vp in [("1f1b", 2, 6, 1),
                                  ("1f1b-interleaved", 4, 6, 2),
                                  ("1f1b-zb", 2, 6, 1)]:
            t = work_table(sched, pp, nm, vp)
            r, g = t.rank_cols, t.glob_cols
            for rank in range(pp):
                lives = {}  # slot -> list of (write, last_read)
                for tk in range(t.span):
                    if r["f_valid"][tk, rank]:
                        key = (int(r["f_c"][tk, rank]),
                               int(r["f_m"][tk, rank]))
                        lives.setdefault(int(r["f_slot"][tk, rank]),
                                         []).append([key, tk, tk])
                for tk in range(t.span):
                    for col, slot_col in (("b_valid", "b_slot"),
                                          ("w_valid", "w_x_slot")):
                        if col == "w_valid" and sched != "1f1b-zb":
                            continue
                        if r[col][tk, rank]:
                            slot = int(r[slot_col][tk, rank])
                            for rec in lives.get(slot, []):
                                kc, km = rec[0]
                                mm = int(r["b_m" if col == "b_valid"
                                           else "w_m"][tk, rank])
                                cc = int(r["b_c"][tk, rank]) \
                                    if col == "b_valid" else kc
                                if (kc, km) == (cc, mm):
                                    rec[2] = max(rec[2], tk)
                for slot, recs in lives.items():
                    recs.sort(key=lambda rec: rec[1])
                    for a, b in zip(recs, recs[1:]):
                        assert a[2] < b[1], (
                            f"{sched} rank {rank} slot {slot}: value "
                            f"{a[0]} (live to {a[2]}) collides with "
                            f"{b[0]} (written {b[1]})")
            assert g["has_f"].any() and g["has_b"].any()

    def test_rejects_non_manual_schedules(self):
        with pytest.raises(ValueError, match="manual-vjp"):
            work_table("wavefront", 2, 4)
        with pytest.raises(ValueError, match="inconsistent"):
            work_table("1f1b", 2, 4, vp=2)
        with pytest.raises(ValueError, match="pp > 1"):
            work_table("1f1b", 1, 4)


def _eqns(jaxpr, out):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eqns(inner, out)
    return out


@functools.lru_cache(maxsize=None)
def _executed(schedule, pp, nm, vp, double_buffer=False):
    """Run one executor on a toy stack over a pipe-only mesh and count what
    ran: ``(stage executions, layer executions, head executions, [(length,
    ppermutes in the body) of each scan that hops])``, all devices summed.

    The mesh has the pipe axis alone, so the executor's ``shard_map`` is
    fully manual and a ``jax.debug.callback`` in the stage fires once a
    device each time the stage body really runs (a ``lax.cond`` branch not
    taken fires nothing).  The wavefront is run forward only: what autodiff
    reruns under ``jax.checkpoint`` is not the executor's to decide."""
    hidden, vocab, layers = 8, 16, 8
    stage_runs, head_runs = [], []
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:pp]), ("pipe",))
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    stack = {"w": jax.random.normal(k[0], (layers, hidden, hidden)) * 0.1}
    params = {
        "embed": jax.random.normal(k[1], (vocab, hidden)),
        "layers": to_interleaved(stack, pp, vp) if vp > 1 else stack,
        "scale": jnp.ones((hidden,)),
        "head": jax.random.normal(k[2], (vocab, hidden)),
    }
    mbs = microbatches(k[0], nm=nm, mb=2, s=4, vocab=vocab)

    def embed_fn(p, mb):
        return p["embed"][mb["input_ids"]]

    def stage_fn(lp, x, mb):
        held = lp["w"].shape[0]
        jax.debug.callback(lambda: stage_runs.append(held))
        for i in range(held):
            x = x + jnp.tanh(x @ lp["w"][i])
        return x

    def head_hidden_fn(hp, y):
        jax.debug.callback(lambda: head_runs.append(1))
        return y * hp["scale"]

    def loss_fn(p, y, mb):
        logits = (y * p["scale"]) @ p["head"].T
        return (jnp.sum(jax.nn.logsumexp(logits, -1)),
                jnp.asarray(float(mb["labels"].size)))

    kw = dict(embed_fn=embed_fn, stage_fn=stage_fn, mesh=mesh,
              virtual_pipeline_size=vp)
    if schedule == "wavefront":
        def fn(p, m):
            return pipeline_loss(p, p["layers"], m, loss_fn=loss_fn, **kw)
    else:
        def fn(p, m):
            return pipeline_loss_and_grad(
                p, p["layers"], m, head_hidden_fn=head_hidden_fn,
                head_params={"scale": p["scale"]}, head_weight=p["head"],
                zero_bubble=schedule == "1f1b-zb",
                double_buffer=double_buffer, **kw)

    with mesh, shd.use_mesh(mesh):
        scans = [
            (e.params["length"],
             sum(x.primitive.name == "ppermute"
                 for x in _eqns(e.params["jaxpr"].jaxpr, [])))
            for e in _eqns(jax.make_jaxpr(fn)(params, mbs).jaxpr, [])
            if e.primitive.name == "scan"]
        jax.block_until_ready(jax.jit(fn)(params, mbs))
        jax.effects_barrier()
    return (len(stage_runs), sum(stage_runs), len(head_runs),
            [sc for sc in scans if sc[1]])


class TestExecutorRealisesTable:
    """What the executors run, counted: the manual-vjp tick loop executes the
    stage body on exactly the table's forward, backward and wgrad ticks (a
    tick no rank works on runs no stage), the head on its head ticks, in one
    scan of ``span`` ticks whose body holds the two ring hops and the two
    routing switches; the wavefront runs every rank on every one of its
    ``nm*vp + pp - 1`` ticks.  The table prices a schedule by these counts
    (``TestWorkTable`` holds the table to the planner)."""

    POINTS = [(2, 4), (2, 16), (4, 8), (4, 6)]

    @pytest.mark.parametrize("pp,nm", POINTS)
    @pytest.mark.parametrize("schedule,vp", [
        ("1f1b", 1), ("1f1b-zb", 1), ("1f1b-interleaved", 2)])
    def test_manual_vjp_runs_the_tables_ticks(self, devices8, schedule, vp,
                                              pp, nm):
        stages, _, heads, scans = _executed(schedule, pp, nm, vp)
        tc = work_table(schedule, pp, nm, vp).tick_counts()
        assert stages == pp * (tc["f_ticks"] + tc["b_ticks"] + tc["w_ticks"])
        assert heads == pp * tc["head_ticks"]
        assert scans == [(tc["span"], 2 + 2 * pp)]

    @pytest.mark.parametrize("pp,nm", POINTS)
    def test_wavefront_runs_every_rank_every_tick(self, devices8, pp, nm):
        vp = 2
        stages, _, _, scans = _executed("wavefront", pp, nm, vp)
        assert stages == pp * (nm * vp + pp - 1)
        assert scans == [(nm * vp + pp - 1, 1 + 2 * pp)]

    @pytest.mark.parametrize("schedule,vp", [
        ("1f1b", 1), ("1f1b-zb", 1), ("1f1b-interleaved", 2)])
    def test_double_buffer_moves_hops_not_work(self, devices8, schedule, vp):
        """Hops hoisted out of their ``cond``s: as many permutes in a tick's
        body, the same scan, the same stage and head executions."""
        assert _executed(schedule, 2, 4, vp, double_buffer=True) \
            == _executed(schedule, 2, 4, vp)

    def test_interleaved_runs_no_more_layers_than_1f1b(self, devices8):
        """At the point the schedules were compared at (pp 2, nm 16, vp 2):
        twice the ticks of half the layers each, less one fill and one drain
        tick's worth — 264 layer executions against 272."""
        _, inter, _, _ = _executed("1f1b-interleaved", 2, 16, 2)
        _, plain, _, _ = _executed("1f1b", 2, 16, 1)
        assert (inter, plain) == (264, 272)


class TestMemoryBound:
    """The schedule's reason to exist, pinned via compiled memory analysis.

    Marginal temp bytes per extra microbatch: the wavefront retains ~2
    activation-sized residuals per microbatch (per-tick stage-input saves +
    the parked/head chain), the 1F1B only the embed feed + its cotangent
    (~1 activation per microbatch per rank) on top of its O(pp) in-flight
    ring.  Measured at nm ∈ {2, 8} on the pp=2 mesh."""

    def test_1f1b_temp_memory_sublinear_in_nm(self, devices8):
        import dataclasses

        from tests.conftest import lower_in_mesh

        cfg = dataclasses.replace(
            CFG, vocab_size=64, hidden_size=256, intermediate_size=256,
            num_attention_heads=2, num_kv_heads=2, max_position_embeddings=128,
        )
        mb, s = 8, 128
        act_bytes = mb * s * cfg.hidden_size * 4  # one fp32 microbatch act
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, FP32)
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, FP32)

        def wf(p, m):
            return pipeline_loss(p, p["layers"], m, embed_fn=embed_fn,
                                 stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh)

        def f1b(p, m):
            return pipeline_loss_and_grad(
                p, p["layers"], m, embed_fn=embed_fn, stage_fn=stage_fn,
                head_hidden_fn=hh, head_params=hp_of(p), head_weight=hw_of(p),
                mesh=mesh)

        temps = {}
        for nm in (2, 8):
            mbs = microbatches(jax.random.PRNGKey(1), nm=nm, mb=mb, s=s,
                               vocab=cfg.vocab_size)
            sh_params, sh_mbs = shard_for(mesh, cfg, params, mbs)
            temps[nm] = (
                lower_in_mesh(mesh, jax.value_and_grad(wf), sh_params, sh_mbs)
                .memory_analysis().temp_size_in_bytes,
                lower_in_mesh(mesh, f1b, sh_params, sh_mbs)
                .memory_analysis().temp_size_in_bytes,
            )
        wf_slope = (temps[8][0] - temps[2][0]) / 6.0
        f1b_slope = (temps[8][1] - temps[2][1]) / 6.0
        detail = {
            "temps": {k: tuple(int(x) for x in v) for k, v in temps.items()},
            "act_bytes": act_bytes,
            "wf_bytes_per_mb": wf_slope, "f1b_bytes_per_mb": f1b_slope,
        }
        # wavefront ~linear: >= 1.4 activation-sized residuals per microbatch
        assert wf_slope >= 1.4 * act_bytes, detail
        # 1F1B sub-linear: only the embed feed + cotangent scale with nm —
        # well under the wavefront's slope and ~1 activation per microbatch
        assert f1b_slope <= 0.75 * wf_slope, detail
        assert f1b_slope <= 1.25 * act_bytes, detail
        # and strictly less absolute temp memory once microbatches stack up
        assert temps[8][1] < temps[8][0], detail


    def test_schedule_memory_comparison(self, devices8):
        """The ISSUE's schedule-comparison bars on compiled peak temp bytes:
        zb stays within 1.15x plain 1F1B (its extra state is one pp-slot dy
        ring + the wgrad re-linearization workspace), and the interleave
        stays at-or-under the autodiff wavefront at the SAME vp (chunk-input
        rings vs ~2 per-layer residuals per work item)."""
        import dataclasses

        from tests.conftest import lower_in_mesh

        cfg = dataclasses.replace(
            CFG, vocab_size=64, hidden_size=256, intermediate_size=256,
            num_attention_heads=2, num_kv_heads=2, max_position_embeddings=128,
        )
        mb, s, nm = 8, 128, 8
        embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, FP32)
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, FP32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mbs = microbatches(jax.random.PRNGKey(1), nm=nm, mb=mb, s=s,
                           vocab=cfg.vocab_size)

        def peak(mesh, sh_params, sh_mbs, *, vp=1, zb=False, wavefront=False):
            if wavefront:
                def fn(p, m):
                    return pipeline_loss(
                        p, p["layers"], m, embed_fn=embed_fn,
                        stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
                        virtual_pipeline_size=vp)
                low = lower_in_mesh(mesh, jax.value_and_grad(fn),
                                    sh_params, sh_mbs)
            else:
                def fn(p, m):
                    return pipeline_loss_and_grad(
                        p, p["layers"], m, embed_fn=embed_fn,
                        stage_fn=stage_fn, head_hidden_fn=hh,
                        head_params=hp_of(p), head_weight=hw_of(p),
                        mesh=mesh, virtual_pipeline_size=vp, zero_bubble=zb)
                low = lower_in_mesh(mesh, fn, sh_params, sh_mbs)
            return low.memory_analysis().temp_size_in_bytes

        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2))
        sh_params, sh_mbs = shard_for(mesh, cfg, params, mbs)
        f1b = peak(mesh, sh_params, sh_mbs)
        zb = peak(mesh, sh_params, sh_mbs, zb=True)

        mesh_vp = build_mesh(MeshConfig(
            pipeline_model_parallel_size=2,
            virtual_pipeline_model_parallel_size=2))
        shp_vp, shm_vp = shard_for(mesh_vp, cfg, params, mbs, vp=2)
        il = peak(mesh_vp, shp_vp, shm_vp, vp=2)
        wf_vp = peak(mesh_vp, shp_vp, shm_vp, vp=2, wavefront=True)

        detail = {"f1b": f1b, "zb": zb, "interleaved": il,
                  "wavefront_vp": wf_vp}
        assert zb <= 1.15 * f1b, detail
        assert il <= wf_vp, detail

    def test_interleave_ring_memory_sublinear_in_nm(self, devices8):
        """The compacted executor's interval-allocated chunk-input ring is
        bounded by the schedule's in-flight window, not by nm: compiled
        temp bytes of the interleave grow by ~1 activation per extra
        microbatch (the embed feed + its cotangent — unavoidable), NOT the
        old lockstep store's ~(vp+2) activations per microbatch."""
        import dataclasses

        from tests.conftest import lower_in_mesh

        cfg = dataclasses.replace(
            CFG, vocab_size=64, hidden_size=256, intermediate_size=256,
            num_attention_heads=2, num_kv_heads=2, max_position_embeddings=128,
        )
        mb, s, vp = 8, 128, 2
        act_bytes = mb * s * cfg.hidden_size * 4
        embed_fn, stage_fn, _lf = llama.pipeline_hooks(cfg, FP32)
        hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, FP32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, FP32)
        mesh = build_mesh(MeshConfig(pipeline_model_parallel_size=2,
                                     virtual_pipeline_model_parallel_size=vp))

        temps = {}
        for nm in (8, 16):
            mbs = microbatches(jax.random.PRNGKey(1), nm=nm, mb=mb, s=s,
                               vocab=cfg.vocab_size)
            shp, shm = shard_for(mesh, cfg, params, mbs, vp=vp)

            def il(p, m):
                return pipeline_loss_and_grad(
                    p, p["layers"], m, embed_fn=embed_fn, stage_fn=stage_fn,
                    head_hidden_fn=hh, head_params=hp_of(p),
                    head_weight=hw_of(p), mesh=mesh, virtual_pipeline_size=vp)

            temps[nm] = lower_in_mesh(mesh, il, shp, shm) \
                .memory_analysis().temp_size_in_bytes
        slope = (temps[16] - temps[8]) / 8.0
        detail = {"temps": temps, "act_bytes": act_bytes,
                  "bytes_per_extra_mb": slope}
        # old lockstep store: (vp+2) = 4 stage inputs per extra microbatch
        # on top of the feed/cotangent pair; the ring bound drops that term
        assert slope <= 2.5 * act_bytes, detail


class TestTrainerDispatch:
    """The trainer builds the 1F1B loss+grad when the gate fires, feeding the
    identical AdamW/ZeRO-1 + metrics + grad-pinning path — one step under
    each schedule must produce the same loss AND grad_norm."""

    def _cfg(self, schedule, arch_overrides=None, vp=1):
        cfg = {
            "name": f"f1b_dispatch_{schedule}",
            "model_source": "hf",
            "seed": 0,
            "trainer": {"max_steps": 1, "log_every_n_steps": 1},
            "distributed_strategy": {
                "pipeline_model_parallel_size": 2,
                "virtual_pipeline_model_parallel_size": vp,
                "pipeline": {"schedule": schedule},
            },
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 16, "synthetic": True},
            "model": {
                "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
                "num_layers": 4, "num_attention_heads": 4,
                "num_key_value_heads": 2, "max_position_embeddings": 32,
                "activations_checkpoint_granularity": None,
                "optim": {"name": "adamw_fp32OptState", "lr": 1e-3,
                          "sched": {"name": "constant"}},
            },
            "precision": {"type": "fp32"},
        }
        if arch_overrides:
            cfg["model"].update(arch_overrides)
        return cfg

    def _one_step(self, schedule, vp=1):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(load_config(self._cfg(schedule, vp=vp)),
                                enable_checkpointing=False)
        batch = next(t.data_module.sharded_batches(t.mesh))
        with t.mesh, shd.use_mesh(t.mesh):
            _, _, metrics = t.train_step(t.params, t.opt_state, batch,
                                         jax.random.PRNGKey(0))
        return t.pipeline_schedule, {k: float(v) for k, v in metrics.items()}

    def test_schedules_produce_identical_step(self, devices8):
        sched_f, m_f = self._one_step("1f1b")
        sched_w, m_w = self._one_step("wavefront")
        assert sched_f == "1f1b" and sched_w == "wavefront"
        np.testing.assert_allclose(m_f["loss"], m_w["loss"], rtol=1e-5)
        np.testing.assert_allclose(m_f["grad_norm"], m_w["grad_norm"], rtol=1e-4)

    def test_zb_produces_identical_step(self, devices8):
        sched_z, m_z = self._one_step("1f1b-zb")
        sched_f, m_f = self._one_step("1f1b")
        assert sched_z == "1f1b-zb"
        np.testing.assert_allclose(m_z["loss"], m_f["loss"], rtol=1e-5)
        np.testing.assert_allclose(m_z["grad_norm"], m_f["grad_norm"],
                                   rtol=1e-4)

    def test_interleaved_produces_identical_step(self, devices8):
        sched_i, m_i = self._one_step("1f1b-interleaved", vp=2)
        sched_w, m_w = self._one_step("wavefront", vp=2)
        assert sched_i == "1f1b-interleaved" and sched_w == "wavefront"
        np.testing.assert_allclose(m_i["loss"], m_w["loss"], rtol=1e-5)
        np.testing.assert_allclose(m_i["grad_norm"], m_w["grad_norm"],
                                   rtol=1e-4)

    def test_auto_resolves_to_1f1b(self, devices8):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(load_config(self._cfg("auto")),
                                enable_checkpointing=False)
        assert t.pipeline_schedule == "1f1b"

    def test_auto_resolves_to_interleaved_under_vp(self, devices8):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        t = Trainer.from_config(load_config(self._cfg("auto", vp=2)),
                                enable_checkpointing=False)
        assert t.pipeline_schedule == "1f1b-interleaved"
        # telemetry: the resolved schedule + the cost model's bubble
        # prediction ride run_facts into run_summary.json
        assert t.run_facts["pipeline_schedule"] == "1f1b-interleaved"
        nm = 2  # gbs=8, mbs=1, dp=4 (8 devices / pp=2)
        assert t.run_facts["bubble_fraction_predicted"] == pytest.approx(
            predicted_bubble_fraction("1f1b-interleaved", 2, nm, 2), abs=1e-6)
        # the compacted executor's per-step trip counts ride run_facts
        ticks = t.run_facts["pipeline_ticks_per_step"]
        assert ticks == work_table("1f1b-interleaved", 2, nm, 2).tick_counts()
        assert ticks["span"] < ticks["lockstep_span"]

    def test_forced_1f1b_on_gpt_raises(self, devices8):
        """The family gate fires at trainer build with the gate's reason —
        not deep inside shard_map."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = self._cfg("1f1b", arch_overrides={"architecture": "gpt"})
        with pytest.raises(ValueError, match="1f1b is unsupported"):
            Trainer.from_config(load_config(cfg), enable_checkpointing=False)
