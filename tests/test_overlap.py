"""Engineered overlap (``optim.overlap``): knob validation with did-you-mean,
bucket-plan legality across the parallelism lattice, bucketed-vs-monolithic
bitwise parity, the compiled update's gather counts and bytes under each
variant, and the XLA_FLAGS merge contract."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.config.loader import load_config
from neuronx_distributed_training_tpu.optim.adamw import (
    AdamWConfig,
    adamw_update,
    init_opt_state,
    opt_state_specs,
)
from neuronx_distributed_training_tpu.optim.overlap import (
    BUCKET_AG_SCOPE,
    OverlapConfig,
    TPU_LHS_FLAGS,
    build_bucket_plan,
    merge_xla_flags,
    xla_lhs_flags,
)
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


# ---------------------------------------------------------------------------
# OverlapConfig validation
# ---------------------------------------------------------------------------


class TestOverlapConfig:
    def test_defaults_all_off(self):
        ov = OverlapConfig.from_config(None)
        assert ov.zero1_bucket_mb == 0.0
        assert ov.prefetch_ag is True  # no-op while bucketing is off
        assert ov.pp_double_buffer is False
        assert ov.xla_lhs is False

    def test_unknown_key_did_you_mean(self):
        with pytest.raises(ValueError,
                           match="did you mean 'zero1_bucket_mb'"):
            OverlapConfig.from_config({"zero1_bucket_md": 32})

    def test_unknown_key_lists_valid(self):
        with pytest.raises(ValueError, match="valid: zero1_bucket_mb"):
            OverlapConfig.from_config({"bogus": 1})

    def test_non_mapping_rejected(self):
        with pytest.raises(ValueError, match="must be a mapping"):
            OverlapConfig.from_config([("zero1_bucket_mb", 32)])

    @pytest.mark.parametrize("bad", [True, "32", None])
    def test_bucket_mb_type_error(self, bad):
        with pytest.raises(ValueError, match="must be a number"):
            OverlapConfig.from_config({"zero1_bucket_mb": bad})

    def test_bucket_mb_negative(self):
        with pytest.raises(ValueError, match="must be >= 0"):
            OverlapConfig.from_config({"zero1_bucket_mb": -1})

    @pytest.mark.parametrize("knob", ["prefetch_ag", "pp_double_buffer",
                                      "xla_lhs"])
    def test_bool_knob_type_error(self, knob):
        with pytest.raises(ValueError, match=f"{knob} must be a bool"):
            OverlapConfig.from_config({knob: 1})

    def test_valid_block(self):
        ov = OverlapConfig.from_config(
            {"zero1_bucket_mb": 64, "prefetch_ag": False,
             "pp_double_buffer": True, "xla_lhs": True})
        assert ov.zero1_bucket_mb == 64.0
        assert ov.prefetch_ag is False
        assert ov.pp_double_buffer is True
        assert ov.xla_lhs is True


class TestLoaderCrossConstraints:
    """``distributed_strategy.overlap`` dies at load time with curated
    messages (the die-before-compile contract)."""

    def _base(self, ds):
        return {
            "distributed_strategy": ds,
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 64},
            "model": {"num_layers": 4, "num_attention_heads": 4},
        }

    def test_bucketing_requires_zero1(self):
        with pytest.raises(ValueError, match="requires[\\s\\S]*zero1: true"):
            load_config(self._base(
                {"zero1": False, "overlap": {"zero1_bucket_mb": 32}}))

    def test_double_buffer_requires_pp(self):
        with pytest.raises(ValueError,
                           match="pp_double_buffer requires[\\s\\S]*pipeline"):
            load_config(self._base({"overlap": {"pp_double_buffer": True}}))

    def test_unknown_key_surfaces_through_loader(self):
        with pytest.raises(ValueError, match="did you mean 'prefetch_ag'"):
            load_config(self._base({"overlap": {"prefetch_agg": True}}))

    @pytest.mark.parametrize("sched", ["1f1b", "1f1b-interleaved"])
    def test_composes_with_1f1b_schedules(self, sched):
        # bucketing + double-buffer ride both manual-VJP schedules
        cfg = load_config(self._base({
            "pipeline_model_parallel_size": 2,
            "virtual_pipeline_model_parallel_size":
                2 if sched == "1f1b-interleaved" else 1,
            "zero1": True,
            "pipeline": {"schedule": sched},
            "overlap": {"zero1_bucket_mb": 32, "pp_double_buffer": True},
        }))
        ov = OverlapConfig.from_config(
            dict(cfg["distributed_strategy"]["overlap"]))
        assert ov.zero1_bucket_mb == 32.0 and ov.pp_double_buffer


# ---------------------------------------------------------------------------
# Bucket-plan legality across the lattice
# ---------------------------------------------------------------------------


def _tiny_tree():
    """Abstract params + specs: a replicated embed, a genuinely TP-sharded
    attn weight (must fall back to the per-leaf gather), a replicated mlp,
    and a 1-D norm scale.  All dims divide 8, so every DP extent works."""
    abstract = {
        "embed": {"w": jax.ShapeDtypeStruct((32, 16), jnp.float32)},
        "layers": {
            "attn": {"w": jax.ShapeDtypeStruct((16, 16), jnp.float32)},
            "mlp": {"w": jax.ShapeDtypeStruct((16, 32), jnp.float32)},
        },
        "norm": {"scale": jax.ShapeDtypeStruct((16,), jnp.float32)},
    }
    pspecs = {
        "embed": {"w": P(None, None)},
        "layers": {"attn": {"w": P(None, "model")},
                   "mlp": {"w": P(None, None)}},
        "norm": {"scale": P(None)},
    }
    return abstract, pspecs


def _group_of(path):
    return path[0].key  # top-level tree key: embed / layers / norm


class TestBucketPlan:
    def _plan(self, mesh, *, bucket_mb, zero1=True, policy=None):
        abstract, pspecs = _tiny_tree()
        ospecs = opt_state_specs(abstract, pspecs, mesh, zero1=zero1,
                                 policy=policy or DtypePolicy())
        return build_bucket_plan(abstract, pspecs, ospecs["mu"], mesh,
                                 bucket_mb=bucket_mb, group_fn=_group_of)

    def test_dp1_mesh_returns_none(self, devices8):
        mesh = build_mesh(MeshConfig(tensor_model_parallel_size=8),
                          devices=devices8)
        assert self._plan(mesh, bucket_mb=1e-6) is None

    def test_tiny_bucket_one_per_group_reversed(self, cpu_mesh):
        plan = self._plan(cpu_mesh, bucket_mb=1e-6)
        assert [b.name for b in plan.buckets] == ["norm", "layers", "embed"]
        assert plan.dp_total == 4 and plan.dp_entry == "data"

    def test_huge_bucket_coalesces_to_one(self, cpu_mesh):
        plan = self._plan(cpu_mesh, bucket_mb=1024)
        assert len(plan.buckets) == 1
        assert plan.buckets[0].name == "norm+layers+embed"

    def test_every_leaf_exactly_once(self, cpu_mesh):
        plan = self._plan(cpu_mesh, bucket_mb=1e-6)
        idxs = [i for b in plan.buckets for i in b.idxs]
        assert sorted(idxs) == list(range(plan.num_leaves))

    def test_tp_sharded_param_falls_back_per_leaf(self, cpu_mesh):
        # attn/w is physically sharded on "model": it must ride a bucket
        # (the update is still bucketed) but NOT the combined gather
        plan = self._plan(cpu_mesh, bucket_mb=1e-6)
        abstract, _ = _tiny_tree()
        leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
        attn_pos = next(i for i, (p, _) in enumerate(leaves)
                        if "attn" in jax.tree_util.keystr(p))
        layers_bucket = next(b for b in plan.buckets if "layers" in b.name)
        assert attn_pos in layers_bucket.idxs
        assert attn_pos not in [a.pos for a in layers_bucket.ag]
        # the replicated leaves all pack
        packed = {a.pos for b in plan.buckets for a in b.ag}
        assert len(packed) == 3  # embed, mlp, norm

    def test_ep_mesh_uses_combined_dp_extent(self, devices8):
        # data=4 x expert=2: the pack extent is the full 8-way DP group
        mesh = build_mesh(MeshConfig(expert_model_parallel_size=2),
                          devices=devices8)
        plan = self._plan(mesh, bucket_mb=1e-6)
        assert plan.dp_total == 8
        assert plan.dp_entry == ("data", "expert")
        assert any(b.ag for b in plan.buckets)

    def test_zero1_off_packs_nothing(self, cpu_mesh):
        # moment specs == param specs: buckets exist (the update partition is
        # still legal) but there is no combined gather to emit
        plan = self._plan(cpu_mesh, bucket_mb=1e-6, zero1=False)
        assert all(not b.ag for b in plan.buckets)


# ---------------------------------------------------------------------------
# Bucketed-vs-monolithic parity (bitwise — same lambdas, different schedule)
# ---------------------------------------------------------------------------


def _materialize(mesh, abstract, pspecs, policy, seed):
    def build(key):
        flat, treedef = jax.tree_util.tree_flatten(abstract)
        keys = jax.random.split(key, len(flat))
        vals = [jax.random.normal(k, x.shape, jnp.float32)
                .astype(policy.param_dtype)
                for k, x in zip(keys, flat, strict=True)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    ns = lambda spec: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(build, out_shardings=ns(pspecs))(
        jax.random.key(seed))
    return params, ns


@pytest.mark.parametrize("tp", [2, 4])          # dp = 8 // tp in {4, 2}
@pytest.mark.parametrize("zero1", [True, False])
@pytest.mark.parametrize("regime", ["mixed_precision", "bf16SR"])
def test_bucketed_matches_monolithic_bitwise(devices8, tp, zero1, regime):
    """The engineered path reorders collectives, not math: params, moments,
    master weights, and metrics must match the monolithic update bit for bit
    across DP extents, ZeRO-1 on/off, and the bf16-params/fp32-master
    regime."""
    mesh = build_mesh(MeshConfig(tensor_model_parallel_size=tp),
                      devices=devices8)
    policy = DtypePolicy.from_precision_config(regime)
    abstract, pspecs = _tiny_tree()
    ospecs = opt_state_specs(abstract, pspecs, mesh, zero1=zero1,
                             policy=policy)
    plan = build_bucket_plan(abstract, pspecs, ospecs["mu"], mesh,
                             bucket_mb=1e-6, group_fn=_group_of)
    assert plan is not None and len(plan.buckets) == 3

    params, ns = _materialize(mesh, abstract, pspecs, policy, seed=tp)
    grads, _ = _materialize(mesh, abstract, pspecs, DtypePolicy(), seed=99)
    cfg = AdamWConfig()

    def step(bucket_plan, params, grads, opt_state):
        return adamw_update(params, grads, opt_state, lr=1e-3, cfg=cfg,
                            policy=policy, bucket_plan=bucket_plan,
                            prefetch_ag=True)

    with mesh, shd.use_mesh(mesh):
        opt_state = jax.jit(
            functools.partial(init_opt_state, policy=policy),
            out_shardings=ns(ospecs))(params)
        mono = jax.jit(functools.partial(step, None))(
            params, grads, opt_state)
        buck_fn = jax.jit(functools.partial(step, plan))
        if zero1:
            # the combined gather actually lowers under its named scope
            hlo = buck_fn.lower(params, grads, opt_state).compile().as_text()
            assert BUCKET_AG_SCOPE in hlo
        buck = jax.jit(functools.partial(step, plan))(
            params, grads, opt_state)

    def assert_tree_equal(a, b):
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), a, b)

    assert_tree_equal(mono[0], buck[0])  # params
    assert_tree_equal(mono[1], buck[1])  # opt state (mu/nu/master/step)
    if regime == "bf16SR":
        assert "master" in mono[1]
    np.testing.assert_array_equal(np.asarray(mono[2]["grad_norm"]),
                                  np.asarray(buck[2]["grad_norm"]))


def test_prefetch_off_still_bitwise(cpu_mesh):
    """prefetch_ag only changes scheduling freedom (barrier chain), never
    values."""
    mesh = cpu_mesh
    policy = DtypePolicy()
    abstract, pspecs = _tiny_tree()
    ospecs = opt_state_specs(abstract, pspecs, mesh, zero1=True,
                             policy=policy)
    plan = build_bucket_plan(abstract, pspecs, ospecs["mu"], mesh,
                             bucket_mb=1e-6, group_fn=_group_of)
    params, ns = _materialize(mesh, abstract, pspecs, policy, seed=3)
    grads, _ = _materialize(mesh, abstract, pspecs, policy, seed=4)
    cfg = AdamWConfig()

    def step(prefetch, params, grads, opt_state):
        return adamw_update(params, grads, opt_state, lr=1e-3, cfg=cfg,
                            policy=policy, bucket_plan=plan,
                            prefetch_ag=prefetch)

    with mesh, shd.use_mesh(mesh):
        opt_state = jax.jit(
            functools.partial(init_opt_state, policy=policy),
            out_shardings=ns(ospecs))(params)
        on = jax.jit(functools.partial(step, True))(params, grads, opt_state)
        off = jax.jit(functools.partial(step, False))(params, grads,
                                                      opt_state)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        (on[0], on[1]), (off[0], off[1]))


# ---------------------------------------------------------------------------
# What bucketing changes in the compiled update: how many gathers, not how
# many bytes
# ---------------------------------------------------------------------------

MESHES = {"tp2": {"tensor_model_parallel_size": 2},
          "tp4": {"tensor_model_parallel_size": 4},
          "dp8": {},
          "ep2": {"expert_model_parallel_size": 2}}

#: bucket sizes of the three variants: none, one bucket, one a grad group
BUCKET_MB = {"off": None, "bucketed-1": 1024.0, "bucketed-N": 1e-6}


def _collectives(compiled):
    """``[(kind, result elements, result bytes, op_name)]`` of a compiled
    program's collectives.  Bytes are as the CPU backend compiles them (it
    widens a bfloat16 collective to float32); elements are the program's."""
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        _SHAPE_RE,
        _shape_bytes,
    )
    from neuronx_distributed_training_tpu.telemetry import census

    out = []
    for text in census.hlo_texts_from_compiled(compiled):
        for line in text.splitlines():
            head, _, meta = line.partition("metadata=")
            m = census._COLLECTIVE_LINE_RE.search(head)
            if not m:
                continue
            shapes = _SHAPE_RE.findall(
                head[head.index("=") + 1: m.start("kind")])
            name = census._OPNAME_META_RE.search(meta)
            out.append((
                m.group("kind"),
                sum(math.prod(int(d) for d in dims.split(",") if d)
                    for _, dims in shapes),
                sum(_shape_bytes(dt, dims) for dt, dims in shapes),
                name.group(1) if name else ""))
    return out


def _bytes_by_kind(collectives):
    out = {}
    for kind, _, nbytes, _ in collectives:
        out[kind] = out.get(kind, 0) + nbytes
    return out


@functools.lru_cache(maxsize=None)
def _compiled_update(mesh_name, regime, zero1, variant, prefetch):
    """The collectives of one step as the trainer jits it (gradients of a
    batch sharded over dp constrained to the params' specs, then
    ``adamw_update``, outputs pinned to the params' and moments' specs),
    with the variant's bucket plan."""
    mesh = build_mesh(MeshConfig(**MESHES[mesh_name]), devices=jax.devices())
    policy = DtypePolicy.from_precision_config(regime)
    abstract, pspecs = _tiny_tree()
    ospecs = opt_state_specs(abstract, pspecs, mesh, zero1=zero1,
                             policy=policy)
    plan = None
    if BUCKET_MB[variant] is not None:
        plan = build_bucket_plan(abstract, pspecs, ospecs["mu"], mesh,
                                 bucket_mb=BUCKET_MB[variant],
                                 group_fn=_group_of)
    ns = lambda specs: jax.tree_util.tree_map(  # noqa: E731
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P))
    placed = lambda shapes, specs: jax.tree_util.tree_map(  # noqa: E731
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, ns(specs))
    params = placed(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, policy.param_dtype),
        abstract), pspecs)
    opt_state = placed(jax.eval_shape(
        functools.partial(init_opt_state, policy=policy), params), ospecs)
    batch = jax.ShapeDtypeStruct(
        (8,), jnp.float32,
        sharding=NamedSharding(mesh, P(("data", "expert"))))

    def loss(params, batch):
        return sum(
            jnp.sum(jnp.tanh(x.astype(jnp.float32)[None]
                             * batch.reshape((-1,) + (1,) * x.ndim)))
            for x in jax.tree_util.tree_leaves(params))

    def step(params, batch, opt_state):
        grads = jax.tree_util.tree_map(
            lambda spec, g: shd.constrain(g, spec), pspecs,
            jax.grad(loss)(params, batch),
            is_leaf=lambda x: isinstance(x, P))
        return adamw_update(params, grads, opt_state, lr=1e-3,
                            cfg=AdamWConfig(), policy=policy,
                            bucket_plan=plan, prefetch_ag=prefetch)

    with mesh, shd.use_mesh(mesh):
        compiled = jax.jit(
            step, out_shardings=(ns(pspecs), ns(ospecs), None)).lower(
                params, batch, opt_state).compile()
    return plan, _collectives(compiled)


@pytest.mark.parametrize("regime", ["mixed_precision", "bf16SR"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_monolithic_update_gathers_each_leaf_once(devices8, mesh_name,
                                                  regime):
    """The reference the variants are held to: ZeRO-1's regather is one
    all-gather a leaf, none under the bucket scope, of exactly the leaves'
    elements a device does not hold."""
    _, collectives = _compiled_update(mesh_name, regime, True, "off", True)
    gathers = [c for c in collectives if c[0] == "all-gather"]
    assert len(gathers) == 4
    assert not [c for c in gathers if BUCKET_AG_SCOPE in c[3]]
    tp = MESHES[mesh_name].get("tensor_model_parallel_size", 1)
    assert sum(c[1] for c in gathers) == 32 * 16 + 16 * 16 // tp \
        + 16 * 32 + 16


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "no-prefetch"])
@pytest.mark.parametrize("variant", ["bucketed-1", "bucketed-N"])
@pytest.mark.parametrize("regime", ["mixed_precision", "bf16SR"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_bucketing_changes_gather_count_not_bytes(devices8, mesh_name, regime,
                                                  variant, prefetch):
    """One all-gather a bucket under ``BUCKET_AG_SCOPE``, each of exactly its
    bucket's packed columns; a leaf sharded over ``model`` keeps its own
    gather; and every collective kind moves the bytes the monolithic update
    moves, with or without the prefetch chain."""
    plan, collectives = _compiled_update(mesh_name, regime, True, variant,
                                         prefetch)
    _, monolithic = _compiled_update(mesh_name, regime, True, "off", True)
    assert all(b.ag for b in plan.buckets)
    scoped = [c for c in collectives
              if c[0] == "all-gather" and BUCKET_AG_SCOPE in c[3]]
    assert len(scoped) == len(plan.buckets)
    assert sorted(c[1] for c in scoped) == sorted(
        plan.dp_total * sum(a.cols for a in b.ag) for b in plan.buckets)
    own_gather = 1 if "tensor_model_parallel_size" in MESHES[mesh_name] else 0
    assert sum(c[0] == "all-gather" for c in collectives) \
        == len(plan.buckets) + own_gather
    assert _bytes_by_kind(collectives) == _bytes_by_kind(monolithic)


@pytest.mark.parametrize("variant", ["bucketed-1", "bucketed-N"])
@pytest.mark.parametrize("mesh_name", ["tp2", "dp8"])
def test_bucketing_without_zero1_adds_no_collective(devices8, mesh_name,
                                                    variant):
    """Moments laid out as the params: nothing to regather, so a bucket plan
    must leave the compiled update's collectives as they are."""
    plan, collectives = _compiled_update(mesh_name, "mixed_precision", False,
                                         variant, True)
    _, monolithic = _compiled_update(mesh_name, "mixed_precision", False,
                                     "off", True)
    assert not any(b.ag for b in plan.buckets)
    assert not [c for c in collectives if c[0] == "all-gather"]
    assert sorted(c[:3] for c in collectives) \
        == sorted(c[:3] for c in monolithic)


# ---------------------------------------------------------------------------
# XLA_FLAGS merging
# ---------------------------------------------------------------------------


class TestMergeXlaFlags:
    def test_append_to_empty(self):
        merged, conflicts = merge_xla_flags("", ("--a=1", "--b=2"))
        assert merged == "--a=1 --b=2" and conflicts == []

    def test_user_flag_wins_and_reports(self):
        merged, conflicts = merge_xla_flags("--a=user", ("--a=ours", "--b=2"))
        assert merged == "--a=user --b=2"
        assert conflicts == [("--a", "--a=user", "--a=ours")]

    def test_identical_duplicate_silent(self):
        merged, conflicts = merge_xla_flags("--a=1", ("--a=1",))
        assert merged == "--a=1" and conflicts == []

    def test_none_base_tolerated(self):
        merged, conflicts = merge_xla_flags(None, ("--a=1",))
        assert merged == "--a=1" and conflicts == []

    def test_lhs_flags_gated_by_platform(self):
        assert xla_lhs_flags("tpu") == TPU_LHS_FLAGS
        assert xla_lhs_flags("cpu") == ()
        assert xla_lhs_flags("TPU") == TPU_LHS_FLAGS
