"""Mixtral: forward/loss with aux load-balancing, EP+TP sharded parity, grads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama, mixtral
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

FP32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                   softmax_dtype=jnp.float32)

CFG = mixtral.MixtralConfig(
    llama=llama.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_attention_heads=4, num_kv_heads=2, max_position_embeddings=32,
        activations_checkpoint_granularity=None,
    ),
    moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                          router_aux_loss_coef=0.02),
)


def _batch(key, b=4, s=16):
    ids = jax.random.randint(key, (b, s), 0, CFG.llama.vocab_size)
    return {"input_ids": ids, "labels": ids}


class TestMixtralForward:
    def test_loss_and_aux(self):
        params = mixtral.init_params(jax.random.PRNGKey(0), CFG, FP32)
        loss, aux = mixtral.forward(params, _batch(jax.random.PRNGKey(1)), CFG, FP32)
        assert loss.shape == ()
        assert np.isfinite(float(loss))
        # router_aux_loss is coefficient-weighted; total = lm + aux
        np.testing.assert_allclose(
            float(loss),
            float(aux["lm_loss"]) + float(aux["router_aux_loss"]),
            rtol=1e-6,
        )
        # weighted LB loss >= coef * uniform minimum (1.0)
        assert float(aux["router_aux_loss"]) >= 0.02

    def test_grads_reach_experts_and_router(self):
        params = mixtral.init_params(jax.random.PRNGKey(0), CFG, FP32)
        batch = _batch(jax.random.PRNGKey(1))

        def loss_fn(p):
            return mixtral.forward(p, batch, CFG, FP32)[0]

        grads = jax.grad(loss_fn)(params)
        g_experts = grads["layers"]["mlp"]["experts"]["gate_up"]
        g_router = grads["layers"]["mlp"]["router"]["w"]
        assert float(jnp.abs(g_experts).sum()) > 0
        assert float(jnp.abs(g_router).sum()) > 0

    def test_dropped_mode_runs(self):
        cfg = mixtral.MixtralConfig(
            llama=CFG.llama,
            moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=False,
                                  capacity_factor=2.0),
        )
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        loss, _ = mixtral.forward(params, _batch(jax.random.PRNGKey(1)), cfg, FP32)
        assert np.isfinite(float(loss))

    def test_from_config_reference_schema(self):
        cfg = mixtral.MixtralConfig.from_config({
            "vocab_size": 320, "hidden_size": 64, "num_layers": 4,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "sliding_window": 128,
            "moe": {"num_experts": 8, "top_k": 2, "dropless": True},
        })
        assert cfg.moe.num_experts == 8
        assert cfg.llama.sliding_window == 128
        assert cfg.moe.capacity_factor is None


class TestMixtralSharded:
    #: 8 devices each: MeshConfig fields -> (token shards of the expert
    #: block, its static row bound: 4 experts, 2 a token, 8 rows of 16 tokens;
    #: twice a chip's fair share is every row there can be, on all three)
    MESHES = {
        "ep2_tp2_dp2": (dict(tensor_model_parallel_size=2,
                             expert_model_parallel_size=2), 4, [128]),
        "ep4_dp2": (dict(expert_model_parallel_size=4), 8, [64]),
        "ep2_tp2_sp": (dict(tensor_model_parallel_size=2,
                            expert_model_parallel_size=2,
                            sequence_parallel=True), 4, [128]),
    }

    @pytest.mark.parametrize("name", list(MESHES))
    def test_ep_tp_parity(self, devices8, name):
        """Sharded loss and every gradient match unsharded, on meshes that
        combine EP with TP, SP and DP; the expert block runs once per token
        shard (``moe_token_shards``), its rows sent to the chips that hold
        their experts (``moe_expert_exchange``).

        Regression pin for the ragged_dot EP hazard: XLA's SPMD partitioner
        has no rule for ragged_dot's GROUP dimension — with the expert dim
        sharded on a strided mesh axis (any EP x TP mesh) it computed each
        shard's local expert slice against the GLOBAL group offsets,
        silently corrupting forward AND backward (loss off ~7e-5, grads off
        ~100% of signal, no error raised).  The kernel sees only a chip's
        resident experts, inside the region that is manual over ``expert``,
        which restores bit-level SPMD parity — so the tolerances here are
        tight: a reappearance of the partitioner hole fails loudly."""
        import dataclasses

        fields, shards, bounds = self.MESHES[name]
        cfg = dataclasses.replace(CFG, llama=dataclasses.replace(
            CFG.llama, sequence_parallel=fields.get("sequence_parallel", False)))
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1), b=8)

        def loss_fn(p, b):
            return mixtral.forward(p, b, cfg, FP32)[0]

        ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)

        mesh = build_mesh(MeshConfig(**fields), devices=devices8)
        specs = mixtral.param_specs(cfg)
        ns = functools.partial(NamedSharding, mesh)
        sh_params = jax.device_put(
            params, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        sh_batch = jax.device_put(batch, ns(P(("data", "expert"))))
        with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(sh_params, sh_batch)
        assert traced == {"moe_token_shards": shards,
                          "moe_expert_exchange": "tokens", "moe_row_bounds": bounds,
                          "remat": {"layers": {"granularity": None, "kept": "all"}}}
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
        ref_leaves, treedef = jax.tree_util.tree_flatten_with_path(ref_grads)
        for (path, rg), g in zip(ref_leaves, treedef.flatten_up_to(grads)):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), rtol=1e-3, atol=1e-5,
                err_msg=jax.tree_util.keystr(path))


def test_mixtral_left_padded_matches_unpadded():
    """attention_mask: left-padded batch matches unpadded on real positions."""
    params = mixtral.init_params(jax.random.PRNGKey(0), CFG, FP32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 3, 128)
    ref, _ = mixtral.forward(params, {"input_ids": ids}, CFG, FP32)
    pad = 4
    padded = jnp.concatenate([jnp.zeros((1, pad), ids.dtype), ids], 1)
    mask = jnp.concatenate(
        [jnp.zeros((1, pad), jnp.int32), jnp.ones((1, 12), jnp.int32)], 1)
    out, _ = mixtral.forward(
        params, {"input_ids": padded, "attention_mask": mask}, CFG, FP32)
    np.testing.assert_allclose(
        np.asarray(out[:, pad:]), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestMoEFrequency:
    """Dense/MoE interleave (reference modeling_mixtral.py:444-451:
    layer i is MoE iff i % frequency == 0)."""

    def _cfg(self, freq):
        import dataclasses

        return mixtral.MixtralConfig(
            llama=dataclasses.replace(CFG.llama, num_layers=4),
            moe=moe_ops.MoEConfig(num_experts=4, top_k=2, dropless=True,
                                  router_aux_loss_coef=0.02),
            moe_frequency=freq,
        )

    def test_interleaved_equals_dense_when_experts_identical(self):
        """With every expert a copy of the dense MLP weights, top-k renorm
        makes MoE(x) == MLP(x): the freq-2 model must match pure llama."""
        cfg = self._cfg(2)
        lc = cfg.llama
        lparams = llama.init_params(jax.random.PRNGKey(0), lc, FP32)
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        # attention/norm trees are identical by construction (same init);
        # make dense sub-layers equal llama's layers 1,3 and experts copies
        # of llama's layers 0,2 MLPs
        g, f, e = 2, 2, 4
        dense_src = jax.tree_util.tree_map(
            lambda x: x.reshape((g, f) + x.shape[1:])[:, 1:], lparams["layers"]["mlp"])
        params["layers"]["mlp"]["dense"] = dense_src
        moe_src_gate_up = np.asarray(lparams["layers"]["mlp"]["gate_up"]["w"]).reshape(
            (g, f) + lparams["layers"]["mlp"]["gate_up"]["w"].shape[1:])[:, 0]
        moe_src_down = np.asarray(lparams["layers"]["mlp"]["down"]["w"]).reshape(
            (g, f) + lparams["layers"]["mlp"]["down"]["w"].shape[1:])[:, 0]
        params["layers"]["mlp"]["moe"]["experts"]["gate_up"] = jnp.asarray(
            np.repeat(moe_src_gate_up[:, None], e, axis=1))
        params["layers"]["mlp"]["moe"]["experts"]["down"] = jnp.asarray(
            np.repeat(moe_src_down[:, None], e, axis=1))

        batch = _batch(jax.random.PRNGKey(1))
        ref_logits, _ = llama.forward(lparams, {"input_ids": batch["input_ids"]},
                                      lc, FP32)
        logits, aux = mixtral.forward(params, {"input_ids": batch["input_ids"]},
                                      cfg, FP32)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   rtol=2e-5, atol=2e-5)

    def test_interleaved_trains(self):
        cfg = self._cfg(2)
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        batch = _batch(jax.random.PRNGKey(1))

        def loss_fn(p):
            return mixtral.forward(p, batch, cfg, FP32)[0]

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        # grads reach the router, experts, AND the dense sub-layers
        assert float(np.abs(np.asarray(
            grads["layers"]["mlp"]["moe"]["router"]["w"])).max()) > 0
        assert float(np.abs(np.asarray(
            grads["layers"]["mlp"]["dense"]["gate_up"]["w"])).max()) > 0

    def test_specs_match_param_tree(self):
        cfg = self._cfg(2)
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)
        specs = mixtral.param_specs(cfg)
        flat_p = jax.tree_util.tree_structure(params)
        flat_s = jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert flat_p == flat_s

    def test_indivisible_raises(self):
        import dataclasses

        cfg = dataclasses.replace(self._cfg(2),
                                  llama=dataclasses.replace(CFG.llama, num_layers=3))
        with pytest.raises(ValueError, match="frequency"):
            mixtral.init_params(jax.random.PRNGKey(0), cfg, FP32)


@pytest.mark.parametrize("ep", [2, 4])
def test_what_crosses_the_expert_axis_in_the_example_step(devices8, ep):
    """The Mixtral example's compiled step (shrunk to 8 devices, ZeRO-1), by
    its collectives over ``expert``.  At ep 2 (x data 2 x model 2) twice a
    chip's fair share of rows is all it can receive: the rows travel
    (all-gathers and all-to-alls under ``moe/dispatch`` and ``moe/combine``)
    and nothing over that axis has an expert weight's shape, whole or split
    over ``model``: no gather of the weights, no reduce-scatter of their
    gradients.  At ep 4 (x model 2) a chip can receive more, and the step
    holds the weights' way as well: there, and only there (a branch, under
    ``experts``), each expert weight is gathered in bf16 and its gradient
    reduce-scattered in float32.  (ZeRO-1 regathers every updated parameter
    over ``data``: not over ``expert``.)"""
    import os
    import re

    from neuronx_distributed_training_tpu.analysis import graph_contract as gc
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
        shrink_overrides,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.telemetry.census import (
        collective_ops_from_texts,
    )
    from neuronx_distributed_training_tpu.trainer.loop import assemble_step_program

    source = os.path.join(os.path.dirname(__file__), "..", "examples", "conf",
                          "hf_mixtral_8x7b_config.yaml")
    # the shrunk widths but for the ffn's, chosen so that no shape of rows
    # (64 tokens a chip, 128 or 256 gathered, hidden 64) reads like a weight's
    cfg = load_config(source, {
        **shrink_overrides(load_config(source), max_devices=8),
        "model.intermediate_size": 160, "model.moe.num_experts": 8,
        "distributed_strategy.expert_model_parallel_size": ep})
    asm = assemble_step_program(cfg, devices=devices8, build_data=False)
    _, compiled = lower_step_program(asm)
    mesh, m = asm.mesh, cfg.model
    assert mesh.shape["expert"] == ep and mesh.shape["model"] == 2
    h, ff, tp = m.hidden_size, m.intermediate_size, mesh.shape["model"]
    weight_tails = {(h, 2 * ff), (h, 2 * ff // tp), (ff, h), (ff // tp, h)}
    partitions, coords = gc._mesh_partitions(mesh), gc._device_coords(mesh)

    rows, weights = set(), set()
    for line in compiled.as_text().splitlines():
        for op in collective_ops_from_texts([line]):
            axes = gc._axes_of_op(op, mesh, partitions, coords) or ()
            if "expert" not in axes:
                continue
            head = line.partition("metadata=")[0]
            shapes = [tuple(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\w+\[([\d,]+)\]", head)]
            if any(len(s) >= 3 and s[-2:] in weight_tails for s in shapes):
                # an expert weight crosses: the weights' way, its branch alone
                assert axes == ("expert",) and re.search(
                    r"moe/shard_map/cond/branch_\d_fun/experts/", op["source_op"]), line
                # (XLA:CPU widens the bf16 gather; tests/test_tpu_compile.py
                # pins its dtype on the chip's compiler)
                dtype = re.match(r"\s*\S+ = \(?(\w+)\[", head).group(1)
                assert op["kind"] == "all-gather" or (
                    op["kind"], dtype) == ("reduce-scatter", "f32"), line
                weights.add(op["kind"])
            elif axes == ("expert",) and "moe/shard_map/" in op["source_op"]:
                assert op["kind"] in ("all-gather", "all-to-all", "all-reduce"), line
                rows.add(op["kind"])
    assert {"all-gather", "all-to-all"} <= rows
    assert weights == (set() if ep == 2 else {"all-gather", "reduce-scatter"})
