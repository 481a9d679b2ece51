"""The ladder a model family with a plain float32 reference climbs
(``benchmark/references/<family>.py``): its seeded weights are the
reference's leaf for leaf; loss and every gradient match under each
rematerialization; three AdamW steps through ``Trainer.from_config(cfg).fit()``
match ``reference.run``; each part the configuration states, left out of the
reference alone, fails that parity; the config refuses by the key's name; the
FLOPs count; the example config trains at toy counts on the CPU mesh.

A family's test file states one ``Toy`` and subclasses ``Ladder`` (or
``BiasLadder`` where a selection bias moves by the load beside the
optimizer).  One rule inside: a case compiles the programs it is about, each
once a file (``Programs``), and dispatches no op by itself on the way there:
weights and tokens are drawn inside one ``jit`` a shape, a tree is compared
by one jitted function returning one small array, and what a case reads of a
forward's ``aux`` comes out of the ``value_and_grad`` it compiled for the loss.
Not a test module: the collector does not pick this file up."""

import dataclasses
import importlib
import inspect
import json
from pathlib import Path
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check as checks
from benchmark.reference import leaf_names
from neuronx_distributed_training_tpu.ops import attention as attn_ops
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ROOT = Path(__file__).resolve().parents[1]
FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
OPTIM = {"lr": 1e-3, "weight_decay": 0.1, "betas": [0.9, 0.95], "eps": 1e-8,
         "sched": {"warmup_steps": 0, "max_steps": 100}}
leaves = jax.tree_util.tree_leaves
COUNTS = "moe_expert_counts"     # what every such family's ``aux`` names its loads by


@dataclasses.dataclass(frozen=True)
class Toy:
    """What a family's file states of itself."""
    module: Any                     # models/<family>.py
    config_class: type
    reference: str                  # the module's name under benchmark.references
    model: Mapping[str, Any]        # the published shape at toy widths, one size for every rung
    seq: int
    shapes: Mapping[str, tuple]     # leaves of the seeded weights whose shape the toy pins
    omissions: tuple                # ``left_out`` names that must each fail parity
    refusals: Mapping[str, tuple]   # id -> (model overrides, distributed_strategy, the name said)
    flops: tuple                    # (model overrides, {part: FLOPs a token at 4096}) pairs
    summary: Mapping[str, Any]      # facts of the three steps' run_summary.json
    #: the example yaml, the toy's keys it keeps its own of, further overrides,
    #: facts of its run_summary.json
    example: tuple
    shares: tuple                   # (a sparse stack, the chips its experts are dealt to) pairs
    bias: tuple = ()                # paths of the selection biases, one a sparse stack
    leaf_tol: float = 2e-5          # a gradient leaf's gap against the reference's norm
    moved: tuple = ("norm",)        # leaves ``spread`` moves off their initial values
    unscaled: tuple = ("embed", "lm_head")   # leaves it does not grow fivefold

    def config(self, **over):
        return self.config_class.from_config({**self.model, **over}, {})


def at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def key_of(seed: int):
    """``jax.random.PRNGKey(seed)`` as the host holds it: nothing dispatched."""
    return np.array([0, seed], np.uint32)


@jax.jit
def leaf_norms(a, b):
    """Per leaf ``|a - b|`` and ``|b|``, one array each for the whole tree."""
    pairs = list(zip(leaves(a), leaves(b), strict=True))
    return (jnp.stack([jnp.linalg.norm(x - y) for x, y in pairs]),
            jnp.stack([jnp.linalg.norm(y) for _, y in pairs]))


@jax.jit
def leaves_equal(a, b):
    return jnp.stack([jnp.all(x == y) for x, y in zip(leaves(a), leaves(b), strict=True)])


def leaf_gaps(a, b) -> tuple:
    """``leaf_norms`` on the host, read once."""
    gap, norm = leaf_norms(a, b)
    return np.asarray(gap), np.asarray(norm)


def worst_gap(a, b) -> float:
    """Largest relative gap of two gradient trees, leaf by leaf."""
    gap, norm = leaf_gaps(a, b)
    return float(np.max(gap / (norm + 1e-30)))


class Programs:
    """One family's programs and the reference's, each compiled once for the
    file that made this object and kept with what they returned."""

    def __init__(self, toy: Toy):
        self.toy = toy
        self.reference = importlib.import_module(f"benchmark.references.{toy.reference}")
        self._kept: dict = {}

    def keep(self, what: tuple, make):
        if what not in self._kept:
            self._kept[what] = make()
        return self._kept[what]

    def model(self, model=None) -> tuple:
        """``(dict, hashable)`` of a model that differs from the toy's, or the toy's."""
        model = dict(self.toy.model if model is None else model)
        return model, json.dumps(model, sort_keys=True, default=str)

    def spread(self, params, seed=9):
        """Norm scales (and what else the toy names) moved off their initial
        values, every other weight grown fivefold and a selection bias off 0
        by about the gap between two experts' scores, so that attention is far
        from uniform and a norm, a gate, a rotation or the bias left out shows."""
        toy = self.toy

        def leaf(path, x):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), sum(map(ord, name)))
            if any(part in name for part in toy.moved):
                return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
            if name.endswith("router/bias"):
                return 0.1 * jax.random.normal(key, x.shape, x.dtype)
            return x * (1.0 if any(part in name for part in toy.unscaled) else 5.0)
        return jax.tree_util.tree_map_with_path(leaf, params)

    def weights(self, seed: int, model=None, *, side="program", spread=True):
        """A side's seeded weights, drawn (and spread) inside one ``jit`` a
        shape; the seed is an argument of it."""
        model, name = self.model(model)

        def make():
            if side == "reference":
                init = lambda k: self.reference.init_params(model, k)  # noqa: E731
            else:
                cfg = self.toy.config_class.from_config(model, {})
                init = lambda k: self.toy.module.init_params(k, cfg, FP32)  # noqa: E731
            return jax.jit(lambda k: self.spread(init(k)) if spread else init(k))

        draw = self.keep(("draw", name, side, spread), make)
        return self.keep(("weights", name, side, spread, seed), lambda: draw(key_of(seed)))

    def tokens(self, seed=1, rows=2, seq=None):
        seq = seq or self.toy.seq
        draw = self.keep(("tokens", rows, seq), lambda: jax.jit(lambda k: jax.random.randint(
            k, (rows, seq), 0, self.toy.model["vocab_size"])))
        return draw(key_of(seed))

    def program(self, params, toks, granularity="full", model=None):
        """``((loss, aux), grads)`` of the family's forward in float32."""
        model, name = self.model(model)

        def make():
            cfg = self.toy.config_class.from_config(
                {**model, "activations_checkpoint_granularity": granularity}, {})
            return jax.jit(jax.value_and_grad(
                lambda p, t: self.toy.module.forward(
                    p, {"input_ids": t, "labels": t}, cfg, FP32), has_aux=True))

        with jax.default_matmul_precision("highest"):
            return self.keep(("program", name, granularity), make)(params, toks)

    def plain(self, params, toks, left_out=(), model=None):
        """``((loss, loads), grads)`` of the reference's, ``loads`` empty
        where the reference counts none."""
        model, name = self.model(model)

        def make():
            c = self.reference.dims(model)

            def loss(p, t):
                out = self.reference.microbatch_loss(p, t, c, left_out=left_out)
                return out if isinstance(out, tuple) else (out, ())
            return jax.jit(jax.value_and_grad(loss, has_aux=True))

        with jax.default_matmul_precision("highest"):
            return self.keep(("plain", name, tuple(left_out)), make)(params, toks)

    def against(self, *, weights: int, tokens: int, granularity="full", left_out=(),
                model=None) -> dict:
        """The program beside the reference on the same spread-out weights and
        tokens: both losses, each gradient leaf's gap and the reference's
        norm by the leaf's name, the trees, the program's ``aux`` and the
        reference's loads.  Either side runs once for what it was given."""
        _, name = self.model(model)
        params, toks = self.weights(weights, model), self.tokens(tokens)
        (loss, aux), grads = self.keep(
            ("ran", name, weights, tokens, granularity),
            lambda: self.program(params, toks, granularity, model))
        (ref_loss, loads), ref_grads = self.keep(
            ("ran plain", name, weights, tokens, tuple(left_out)),
            lambda: self.plain(params, toks, left_out, model))
        gap, norm = leaf_gaps(grads, ref_grads)
        return {"loss": float(loss), "ref_loss": float(ref_loss), "names": leaf_names(grads),
                "gap": gap, "norm": norm, "worst": float(np.max(gap / (norm + 1e-30))),
                "grads": grads, "ref_grads": ref_grads, "aux": aux, "loads": loads}

    def shares(self, stack: str, chips: int) -> dict:
        """One sparse layer's MLP of ``stack`` with every expert in one program
        (``whole``, its ``counts`` where the route counts), what each of
        ``chips`` makes of the range it holds (``parts``, ``part_counts``) with
        the shared expert counted once (``total``), and the reference's block
        uncut (``uncut``, ``loads`` where it counts them): one program."""
        toy, ref = self.toy, self.reference
        uncut_model = {**toy.model, "num_experts_held": None}
        moe = toy.config_class.from_config(uncut_model, {}).moe
        per, hidden = moe.num_experts // chips, toy.model["hidden_size"]
        # the whole range: Laguna's reference reads it from ``c``, the later ones
        # take it as ``held`` and count the loads
        c = {**ref.dims(toy.model), "lo": 0, "hi": moe.num_experts}
        takes_held = "held" in inspect.signature(ref.expert_block).parameters

        @jax.jit
        def run(params, key):
            layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"][stack]["mlp"])
            z = jax.random.normal(key, (2, toy.seq, hidden), jnp.float32)

            def block(p, held):
                return moe_ops.moe_block(p, z, dataclasses.replace(moe, experts_held=held),
                                         compute_dtype=jnp.float32)

            whole, aux = block(layer, None)
            routed = {k: v for k, v in layer.items() if k != "shared"}
            parts = [block({**routed, "experts": jax.tree_util.tree_map(
                lambda a, lo=lo: a[lo:lo + per], layer["experts"])}, (lo, lo + per))
                for lo in range(0, moe.num_experts, per)]
            total = sum(y for y, _ in parts)
            if "shared" in layer:
                total = total + moe_ops._shared_expert(layer["shared"], z, jnp.float32, moe.act)
            uncut, loads = ref.expert_block(
                layer, z.reshape(-1, hidden), c, ref.plain._matmul(None),
                **({"held": (0, moe.num_experts)} if takes_held else {}))
            return {"whole": whole, "counts": aux.get("expert_counts"),
                    "parts": [y for y, _ in parts], "total": total,
                    "part_counts": [a["expert_counts"] for _, a in parts if "expert_counts" in a],
                    "uncut": uncut.reshape(whole.shape), "loads": loads if takes_held else None}

        with jax.default_matmul_precision("highest"):
            return jax.tree_util.tree_map(np.asarray, run(self.weights(2, uncut_model), key_of(3)))


def gradients_match(found: dict, leaf_tol: float):
    """The rung's comparison, also for a family's one deeper shape."""
    assert found["loss"] == pytest.approx(found["ref_loss"], rel=2e-6)
    for name, gap, norm in zip(found["names"], found["gap"], found["norm"], strict=True):
        assert gap <= leaf_tol * norm, name


def bias_steers_unweighed(found: dict, toy: Toy):
    """The bias's gradient is exactly zero on both sides, and the loads the
    rule reads are the reference's, layer for layer, expert for expert."""
    for path in toy.bias:
        assert not np.any(np.asarray(at(found["grads"], path)))
        assert not np.any(np.asarray(at(found["ref_grads"], path)))
    counts = [np.asarray(v) for k, v in sorted(found["aux"].items()) if k.startswith(COUNTS)]
    for mine, theirs in zip(counts, leaves(found["loads"]), strict=True):
        np.testing.assert_array_equal(mine, np.asarray(theirs))
    sparse_layers = sum(at(found["grads"], path).shape[0] for path in toy.bias)
    assert sum(c.sum() for c in counts) == (                           # layers x tokens x k
        sparse_layers * 2 * toy.seq * toy.config().moe.top_k)


def flash_matches_core(seed, b, heads, kv_heads, d_qk, d_v, rows=None, window=None):
    """The flash kernels, interpret mode, against core attention at 256
    tokens under tiles of 128: forward and all three gradients, causal, also
    under a padding mask, packed segments or a window.  Operands drawn and both
    sides run inside one program."""
    s = 256

    @jax.jit
    def both(key):
        ks = jax.random.split(key, 4)
        q, k, v, ct = (jax.random.normal(kk, (b, s, n, d), jnp.float32) for kk, n, d in zip(
            ks, (heads, kv_heads, kv_heads, heads), (d_qk, d_qk, d_v, d_v)))
        mask = (jnp.arange(s)[None, :] < jnp.array([[s], [s - 70]]))[:b]
        segments = jnp.stack([jnp.arange(s) // 100, jnp.arange(s) // 64])[:b]
        kw, bias, keep = {}, None, 1.0
        if rows == "attention_mask":
            kw, bias = {"attention_mask": mask}, attn_ops.padding_mask_bias(mask)
            keep = mask[:, :, None, None]
        if rows == "segment_ids":
            kw, bias = {"segment_ids": segments}, attn_ops.segment_mask_bias(segments)

        def side(fn):
            def loss(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * ct * keep), out * keep
            (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
            return out, grads

        return (side(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True, sliding_window=window, block_q=128, block_kv=128,
                    interpret=True, **kw)),
                side(lambda q, k, v: attn_ops.core_attention(
                    q, k, v, causal=True, sliding_window=window, bias=bias)))

    with jax.default_matmul_precision("highest"):
        (out, grads), (core, core_grads) = both(key_of(seed))
    assert out.shape == (b, s, heads, d_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(core), rtol=2e-4, atol=2e-4)
    for a, c in zip(grads, core_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-3)


class Ladder:
    """The rungs, as the test methods of the class a family's file makes of
    this one with its ``toy``.  The file's own module-scoped ``programs``
    fixture, ``Programs(toy)``, serves them and the file's other cases."""

    toy: Toy

    def pytest_generate_tests(self, metafunc):
        toy = metafunc.cls.toy
        for name, values, ids in (
                ("omission", toy.omissions, None),
                ("refusal", list(toy.refusals.values()), list(toy.refusals)),
                ("bias", toy.bias, ["/".join(path) for path in toy.bias]),
                ("shares", toy.shares, [f"{stack}-{chips}" for stack, chips in toy.shares])):
            if name in metafunc.fixturenames:
                metafunc.parametrize(name, values, ids=ids)

    # -- against the reference --------------------------------------------------

    def test_the_seeded_weights_are_the_references_leaf_for_leaf(self, programs):
        mine = programs.weights(11, spread=False)
        theirs = programs.weights(11, side="reference", spread=False)
        names = leaf_names(mine)
        assert names == leaf_names(theirs)
        for name, a, b, same in zip(names, leaves(mine), leaves(theirs),
                                    np.asarray(leaves_equal(mine, theirs)), strict=True):
            assert a.shape == b.shape and same, name
        shapes = {name: leaf.shape for name, leaf in zip(names, leaves(mine))}
        assert {name: shapes.get(name) for name in self.toy.shapes} == dict(self.toy.shapes)
        assert sorted(mine["layers"]) == sorted(      # the toy pins a leaf of every stack
            {name.split("/")[1] for name in self.toy.shapes if name.startswith("layers/")})
        for path in self.toy.bias:
            assert not np.any(np.asarray(at(mine, path)))
        specs = self.toy.module.param_specs(self.toy.config())
        is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
        assert jax.tree_util.tree_structure(specs, is_leaf=is_spec) == jax.tree_util.tree_structure(mine)
        for spec, leaf in zip(leaves(specs, is_leaf=is_spec), leaves(mine)):
            assert len(spec) == leaf.ndim

    @pytest.mark.parametrize("granularity", [None, "selective", "full"], ids=str)
    def test_loss_and_every_gradient_match_the_reference_in_float32(self, programs, granularity):
        gradients_match(programs.against(weights=3, tokens=1, granularity=granularity),
                        self.toy.leaf_tol)

    @pytest.fixture(scope="class")
    def trained(self, programs, tmp_path_factory):
        """``Trainer.from_config(cfg).fit()`` in float32, three steps of two
        micro-batches of two rows, beside ``reference.run`` on the same rows:
        the trainer, its log's rows, its ``run_summary.json``, the reference's
        numbers and the program's beside them."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.data.loader import DataModule
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        toy, seed, rows = self.toy, 5, 4
        steps = [np.asarray(programs.tokens(seed=100 + k, rows=rows)) for k in range(3)]

        class Rows(DataModule):
            def fetch_rows(self, idx):
                return {"input_ids": np.stack([steps[i // rows][i % rows] for i in idx])}

        cfg = load_config({
            "seed": seed,
            "model": {**toy.model, "optim": {"name": "adamw_fp32OptState", **OPTIM}},
            "distributed_strategy": {"tensor_model_parallel_size": 1},
            "data": {"global_batch_size": rows, "micro_batch_size": 2, "seq_length": toy.seq},
            "trainer": {"max_steps": 3, "log_every_n_steps": 1, "gradient_clip_val": 1.0},
            "exp_manager": {"exp_dir": str(tmp_path_factory.mktemp(toy.reference)),
                            "name": toy.reference},
            "precision": {"type": "fp32"}})
        trainer = Trainer.from_config(cfg, data_module=Rows(1 << 10, rows),
                                      devices=jax.devices()[:1], enable_checkpointing=False)
        with jax.default_matmul_precision("highest"):
            trainer.fit()
        log_dir = Path(trainer.exp.log_dir)
        logged = [json.loads(line) for line in open(log_dir / "metrics.jsonl")]
        ref = programs.reference.run(toy.model, OPTIM, 1.0,
                                     [s.reshape(2, 2, toy.seq) for s in steps], seed)
        return {"trainer": trainer, "logged": logged, "ref": ref,
                "summary": json.load(open(log_dir / "run_summary.json")),
                "dparam": checks.parameter_change_norms(programs.reference, trainer.params,
                                                        toy.model, seed),
                "grad1": checks.first_gradient_norms(programs.reference, trainer.opt_state, 0.9)}

    def test_three_steps_match_the_reference_in_float32(self, trained):
        """The losses of three steps, the first gradient's leaves and the
        parameters' change, leaf by leaf (a selection bias among them: three
        steps of the rule on both sides); the held rows under their bound."""
        ref = trained["ref"]
        assert [r["loss"] for r in trained["logged"]] == pytest.approx(ref["loss"], rel=1e-5)
        gaps = checks.leaf_gaps(trained["dparam"], ref["dparam"])
        assert max(gaps.values()) < 2e-4, max(gaps, key=gaps.get)
        assert set(trained["grad1"]) == set(ref["grad1"])
        for r in trained["logged"]:
            assert r["moe/row_bound"] == 0.0 and r["moe/held_rows"] > 0
            # the narrowest operand that held the largest layer's rows
            assert r["moe/held_rows_share"] <= r["moe/held_operand"] <= moe_ops._HELD_ROWS[-1] * 1.1
        summary = trained["summary"]
        assert {k: summary.get(k) for k in self.toy.summary} == dict(self.toy.summary)

    # -- the comparison is tight enough: what is left out shows -------------------

    def test_nothing_left_out_is_parity(self, programs):
        found = programs.against(weights=7, tokens=4)
        assert abs(found["loss"] - found["ref_loss"]) < 1e-5 and found["worst"] < 5e-5

    def test_an_omission_fails_parity(self, programs, omission):
        """Each part of a layer that the configuration states, left out of the
        reference alone, moves a gradient leaf by a hundred times the rounding."""
        found = programs.against(weights=7, tokens=4, left_out=(omission,))
        assert found["worst"] > 5e-3, (omission, found["loss"] - found["ref_loss"], found["worst"])

    # -- the experts' shares add up ------------------------------------------------

    def test_the_shares_of_all_held_ranges_make_the_layer(self, programs, shares):
        """A sparse layer's MLP output with all experts in one program equals
        the sum over the chips of what each makes of the experts it holds,
        plus a shared expert counted once; and both equal the uncut
        reference.  Every chip routes over all experts and counts the same
        loads; a share alone is not the layer."""
        found = programs.shares(*shares)
        np.testing.assert_allclose(found["total"], found["whole"], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(found["whole"], found["uncut"], rtol=1e-4, atol=1e-5)
        for counts in found["part_counts"]:
            np.testing.assert_array_equal(counts, found["counts"])
        if found["loads"] is not None:
            np.testing.assert_array_equal(found["loads"], found["counts"])
        assert np.linalg.norm(found["parts"][0] - found["whole"]) > 0.1 * np.linalg.norm(
            found["whole"])

    # -- what is not wired is refused by name; what is, is counted ----------------

    def test_the_config_refuses_by_the_keys_name(self, refusal):
        model, ds, named = refusal
        with pytest.raises(ValueError, match=named):
            self.toy.config_class.from_config({**self.toy.model, **model}, ds)

    def test_the_flops_count(self):
        for over, parts in self.toy.flops:
            counted = self.toy.module.flops_breakdown(self.toy.config(**over), 4096)
            assert {k: counted[k] for k in parts} == pytest.approx(parts, rel=1e-12), over

    # -- through nxdt-train -------------------------------------------------------

    def test_the_example_config_trains_at_toy_counts_on_the_cpu_mesh(self, tmp_path, devices8):
        """The family's ``examples/conf`` yaml at toy counts through
        ``Trainer.from_config(cfg).fit()`` on ep 4 x dp 2: every expert resident
        somewhere, the rows exchanged between the chips that hold them, a
        selection bias moving by the loads summed over the chips."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        toy = self.toy
        yaml, own, over, facts = toy.example
        cfg = load_config(str(ROOT / "examples/conf" / yaml), {
            **{f"model.{k}": v for k, v in toy.model.items()
               if k not in ("architecture", "num_experts_held", *own)},
            "model.fusions.flash_attention": False,
            "distributed_strategy.expert_model_parallel_size": 4,
            "data.synthetic": True, "data.seq_length": toy.seq, "data.global_batch_size": 8,
            "trainer.max_steps": 3, "trainer.log_every_n_steps": 1,
            "exp_manager.exp_dir": str(tmp_path), "exp_manager.resume_if_exists": False,
            "exp_manager.checkpoint_callback_params": None,
            "debug": {"validate_sharding": True}, **over})
        trainer = Trainer.from_config(cfg, devices=devices8, enable_checkpointing=False)
        trainer.fit()
        log_dir = Path(trainer.exp.log_dir)
        rows = [json.loads(line) for line in open(log_dir / "metrics.jsonl")]
        assert [r["step"] for r in rows] == [1, 2, 3]
        assert all(np.isfinite(r["loss"]) and r["moe/recv_rows_share_max"] >= 1.0 for r in rows)
        summary = json.load(open(log_dir / "run_summary.json"))
        assert summary["model_family"] == toy.config_class.__name__
        assert summary["moe_token_shards"] == 8 and "moe_experts_held" not in summary
        assert {k: summary.get(k) for k in facts} == dict(facts)
        if toy.bias:
            assert [r["moe/bias_abs_max"] for r in rows] == pytest.approx([0.0, 0.001, 0.002])
        for path in toy.bias:
            steps = np.asarray(at(trainer.params, path), np.float64) / 0.001
            np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)


class BiasLadder(Ladder):
    """The rungs more of a family whose experts are chosen by ``sigmoid score +
    bias``, the bias moving by the load after every optimizer step and weighed
    nowhere."""

    @pytest.mark.parametrize("granularity", [None, "selective", "full"], ids=str)
    def test_loss_and_every_gradient_match_the_reference_in_float32(self, programs, granularity):
        """... and the bias steers and is never weighed: its gradient is exactly
        zero on both sides; the loads the rule reads are the reference's, layer
        for layer, expert for expert."""
        super().test_loss_and_every_gradient_match_the_reference_in_float32(programs, granularity)
        bias_steers_unweighed(programs.against(weights=3, tokens=1, granularity=granularity),
                              self.toy)

    def test_three_steps_match_the_reference_in_float32(self, trained):
        super().test_three_steps_match_the_reference_in_float32(trained)
        moe = self.toy.config().moe
        for path in self.toy.bias:   # half of it a step of 0.001 away at the least
            elements = at(trained["trainer"].params, path).size
            assert trained["dparam"]["/".join(path)] > 0.001 * np.sqrt(elements) * 0.5
        for r in trained["logged"]:
            assert 1.0 <= r["moe/load_max_share"] < moe.num_experts / moe.top_k
            assert not any(k.startswith(COUNTS) for k in r)
        assert [r["moe/bias_abs_max"] for r in trained["logged"]] == pytest.approx(
            [0.0, 0.001, 0.002])

    def test_the_bias_moves_by_the_rule_and_by_nothing_of_adamws(self, trained, bias):
        """After three steps every element of the bias is a whole number of
        steps of 0.001 (no decay, no moment's step mixed in) and the
        optimizer's moments for it are exactly zero."""
        trainer = trained["trainer"]
        steps = np.asarray(at(trainer.params, bias), np.float64) / 0.001
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
        assert set(np.round(steps).astype(int).ravel()) <= {-3, -2, -1, 0, 1, 2, 3}
        assert np.any(np.round(steps) != 0)
        for moment in ("mu", "nu"):
            assert not np.any(np.asarray(at(trainer.opt_state[moment], bias)))

    def test_an_omitted_bias_update_shows_in_the_parameters_change(self, programs):
        """The rule left out of the reference's step: the bias's change reads 0
        there and the comparison 1 (a state left unchanged)."""
        toy = self.toy
        steps = [np.asarray(programs.tokens(seed=100 + k))[None] for k in range(3)]
        # the cells' regime: a small rate under its warm-up, so that the weights
        # move by less than the bias's steps of 0.001
        optim = {**OPTIM, "lr": 1e-5, "sched": {"warmup_steps": 100, "max_steps": 1000}}
        with_rule = programs.reference.run(toy.model, optim, 1.0, steps, 5)
        without = programs.reference.run(toy.model, optim, 1.0, steps, 5,
                                         left_out=("bias_update",))
        names = ["/".join(path) for path in toy.bias]
        gaps = checks.leaf_gaps(without["dparam"], with_rule["dparam"])
        for name in names:
            assert without["dparam"][name] == 0.0 < with_rule["dparam"][name]
            assert with_rule["grad1"][name] == 0.0
            assert gaps[name] == pytest.approx(1.0)
        assert max(v for k, v in gaps.items() if k not in names) < 0.1
