"""The looped decoder (``models/ouro.py``): one stack applied
``total_ut_steps`` times with shared weights, a head and an exit gate at the
end of every pass.  Held against the benchmark's plain reference
(``benchmark/references/ouro.py``, float32, nothing of the
program), tied to
``llama.forward`` at one pass, and shown to be compared tightly enough: each
of seven omissions breaches a limit at this size."""

import dataclasses
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import check as checks
from neuronx_distributed_training_tpu.models import llama, ouro
from neuronx_distributed_training_tpu.ops import cross_entropy as ce_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ROOT = Path(__file__).resolve().parents[1]
MODEL = dict(architecture="ouro", vocab_size=256, hidden_size=64, intermediate_size=128,
             num_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             total_ut_steps=4, exit_entropy_beta=0.1, rope_theta=1e6, rms_norm_eps=1e-6,
             sliding_window=32, initializer_range=0.02,
             activations_checkpoint_granularity="selective")
OPTIM = {"lr": 1e-3, "weight_decay": 0.1, "betas": [0.9, 0.95], "eps": 1e-8,
         "sched": {"warmup_steps": 0, "max_steps": 100}}
FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
MIXED = DtypePolicy.from_precision_config({"type": "mixed_precision"})
#: the limits the configuration's cell is held to at toy widths
LIMITS = json.loads(
    (ROOT / "tests" / "benchmark" / "toy_limits_ouro.json").read_text())["limits"]


@pytest.fixture(scope="module")
def reference():
    return importlib.import_module("benchmark.references.ouro")


def config(**over):
    post = over.pop("post_sublayer_norms", True)
    cfg = ouro.OuroConfig.from_config({**MODEL, **over}, {})
    return dataclasses.replace(
        cfg, llama=dataclasses.replace(cfg.llama, post_sublayer_norms=post))


def tokens(seed=1, rows=2, seq=64):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, MODEL["vocab_size"])


def batch_of(toks):
    return {"input_ids": toks, "labels": toks}


def jittered(params, seed=9):
    """Norm scales and the gate's bias moved off their initial 1 and 0, so
    that a scale or a bias left out would show."""
    def leaf(path, x):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if "norm" in name or "bias" in name:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), sum(map(ord, name)))
            return x + 0.1 * jax.random.normal(key, x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(leaf, params)


def looped(params, toks, cfg, policy, *, stacks=None, carry_unnormed=False,
           stop_gate=False, last_head_only=False, last_pass_gradient_only=False):
    """The program's own pieces (``llama.embed_and_rope`` / ``decoder_stack`` /
    ``logits_fn``, ``ops``, ``ouro.exit_distribution``) put together pass by
    pass, unrolled, with one omission switched on at a time; with none it is
    ``ouro.forward``.  ``stacks`` gives every pass a weight copy of its own."""
    lc, passes = cfg.llama, cfg.total_ut_steps
    labels = toks[:, 1:]
    h, cos, sin = llama.embed_and_rope(params, toks, lc, policy)
    ces, zs = [], []
    for t in range(passes):
        layers = params["layers"] if stacks is None else stacks[t]
        if last_pass_gradient_only and t < passes - 1:
            layers = jax.lax.stop_gradient(layers)
        raw = llama.decoder_stack(layers, h, cos, sin, lc, policy)
        normed = norm_ops.apply_rms_norm(params["final_norm"], raw, eps=lc.rms_norm_eps)
        logits = llama.logits_fn(params, normed, lc, policy)[:, :-1]
        ces.append(ce_ops.cross_entropy_loss(logits, labels, reduction="none"))
        gate = params["exit_gate"]
        zs.append(jnp.sum(normed[:, :-1].astype(jnp.float32) * gate["w"][:, 0], axis=-1)
                  + gate["bias"])
        h = raw if carry_unnormed else normed
    ce, z = jnp.stack(ces), jnp.stack(zs)
    if stop_gate:
        z = jax.lax.stop_gradient(z)
    p, entropy = ouro.exit_distribution(z)
    if last_head_only:
        p = jnp.zeros_like(p).at[-1].set(1.0)
        entropy = jnp.zeros_like(entropy)
    return jnp.sum(jnp.sum(p * ce, axis=0) - cfg.exit_entropy_beta * entropy) / labels.size


# -- against the plain reference ---------------------------------------------


def test_loss_and_every_leafs_gradient_match_the_reference_in_float32(reference):
    cfg, toks = config(), tokens()
    params = jittered(ouro.init_params(jax.random.PRNGKey(3), cfg, FP32))
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: ouro.forward(p, batch_of(toks), cfg, FP32), has_aux=True))(params)
        (ref_loss, ref_aux), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.microbatch_loss(p, toks, reference.dims(MODEL)),
            has_aux=True))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    names = reference.leaf_names(grads)
    assert {"exit_gate/w", "exit_gate/bias", "final_norm/scale",
            "layers/input_norm/scale", "layers/input_norm_2/scale",
            "layers/post_attn_norm/scale", "layers/post_attn_norm_2/scale"} <= set(names)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(g - r)) <= 1e-5 * float(jnp.linalg.norm(r)), name
    # the logged columns are the reference's per-pass means
    for t in range(cfg.total_ut_steps):
        assert float(aux[f"loss/ce_pass{t + 1}"]) == pytest.approx(float(ref_aux["ce"][t]), rel=1e-5)
        assert float(aux[f"exit/p_pass{t + 1}"]) == pytest.approx(float(ref_aux["p"][t]), rel=1e-5)
    assert float(aux["exit/entropy"]) == pytest.approx(float(ref_aux["entropy"]), rel=1e-5)
    assert sum(float(aux[f"exit/p_pass{t + 1}"]) for t in range(4)) == pytest.approx(1.0, abs=1e-5)


def test_the_seeded_weights_are_the_references_leaf_for_leaf(reference):
    cfg = config()
    key = jax.random.PRNGKey(11)
    mine, theirs = ouro.init_params(key, cfg, FP32), reference.init_params(MODEL, key)
    assert reference.leaf_names(mine) == reference.leaf_names(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    specs = ouro.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    ) == jax.tree_util.tree_structure(mine)
    # the new leaves are replicated
    assert specs["exit_gate"]["w"] == jax.sharding.PartitionSpec(None, None)
    assert specs["layers"]["input_norm_2"]["scale"] == jax.sharding.PartitionSpec(None, None)


@pytest.mark.parametrize("granularity", [None, "selective", "full"])
def test_one_pass_without_post_norms_is_llama_forward(granularity):
    """T = 1: the exit distribution is [1], its entropy 0, the gate unused;
    without the post-sub-layer norms the loop is ``llama.forward`` bit for bit,
    on ``llama``'s own parameters (no gate, two norms a layer)."""
    cfg = config(total_ut_steps=1, post_sublayer_norms=False,
                 activations_checkpoint_granularity=granularity)
    assert cfg.llama == llama.LlamaConfig.from_config(
        {**MODEL, "activations_checkpoint_granularity": granularity}, {})
    params = jittered(llama.init_params(jax.random.PRNGKey(5), cfg.llama, MIXED))
    batch = batch_of(tokens(seed=2))
    (mine, aux), g_mine = jax.jit(jax.value_and_grad(
        lambda p: ouro.forward(p, batch, cfg, MIXED), has_aux=True))(params)
    (theirs, _), g_theirs = jax.jit(jax.value_and_grad(
        lambda p: llama.forward(p, batch, cfg.llama, MIXED), has_aux=True))(params)
    assert float(mine) == float(theirs)
    assert float(aux["exit/p_pass1"]) == 1.0 and float(aux["exit/entropy"]) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(g_mine), jax.tree_util.tree_leaves(g_theirs)):
        assert bool(jnp.all(a == b))


def test_the_fused_chunked_head_gives_the_same_per_pass_losses():
    """``fusions.chunked_ce`` says how the head is computed, here as in
    ``llama.forward``: per-token CE of every pass without its logits."""
    toks = tokens(seed=8)
    plain_cfg, chunked_cfg = config(), config(fusions={"chunked_ce": 4})
    assert chunked_cfg.llama.vocab_chunks == 4 and plain_cfg.llama.vocab_chunks is None
    params = jittered(ouro.init_params(jax.random.PRNGKey(2), plain_cfg, FP32))
    outs = [jax.jit(jax.value_and_grad(
        lambda p, c=c: ouro.forward(p, batch_of(toks), c, FP32), has_aux=True))(params)
        for c in (plain_cfg, chunked_cfg)]
    ((plain_loss, plain_aux), plain_g), ((loss, aux), g) = outs
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-6)
    assert all(float(aux[k]) == pytest.approx(float(plain_aux[k]), rel=1e-5) for k in aux)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(plain_g)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-9


def test_exit_distribution_sums_to_one_and_survives_a_saturated_gate():
    z = jnp.array([[-3.0, 0.2, 90.0, -90.0], [1.0, -0.5, 90.0, -90.0],
                   [0.3, 2.0, -90.0, 90.0], [7.0, 7.0, 7.0, 7.0]])
    p, entropy = ouro.exit_distribution(z)
    assert jnp.allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(p))) and bool(jnp.all(jnp.isfinite(entropy)))
    g = jax.nn.sigmoid(z)
    assert jnp.allclose(p[0], g[0]) and jnp.allclose(p[1], g[1] * (1 - g[0]))
    assert jnp.allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), atol=1e-7)
    grads = jax.grad(lambda zz: jnp.sum(ouro.exit_distribution(zz)[1]))(z)
    assert bool(jnp.all(jnp.isfinite(grads))) and bool(jnp.all(grads[-1] == 0.0))


def test_a_shared_weights_gradient_is_the_sum_over_the_passes():
    cfg, toks = config(activations_checkpoint_granularity="full"), tokens(seed=4)
    params = jittered(ouro.init_params(jax.random.PRNGKey(6), cfg, FP32))
    with jax.default_matmul_precision("highest"):
        shared = jax.jit(jax.grad(
            lambda p: ouro.forward(p, batch_of(toks), cfg, FP32)[0]))(params)["layers"]
        copies = [params["layers"]] * cfg.total_ut_steps
        per_pass = jax.jit(jax.grad(
            lambda stacks: looped(params, toks, cfg, FP32, stacks=stacks)))(copies)
    assert len(per_pass) == 4
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_pass)
    for path, a in jax.tree_util.tree_flatten_with_path(shared)[0]:
        b = summed
        for k in path:
            b = b[k.key]
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(a)), path
        # and no single pass gives it
        one = per_pass[-1]
        for k in path:
            one = one[k.key]
        assert float(jnp.linalg.norm(a - one)) > 0.1 * float(jnp.linalg.norm(a)), path


# -- the comparison is tight enough ------------------------------------------

OMISSIONS = {
    "three passes for four": dict(cfg=dict(total_ut_steps=3)),
    "post-norms left out": dict(cfg=dict(post_sublayer_norms=False)),
    "un-normed state carried": dict(flags=dict(carry_unnormed=True)),
    "beta = 0": dict(cfg=dict(exit_entropy_beta=0.0)),
    "gradient stopped through the gate": dict(flags=dict(stop_gate=True)),
    "head on the last pass only": dict(flags=dict(last_head_only=True)),
    "gradient from one pass only": dict(flags=dict(last_pass_gradient_only=True)),
}


@pytest.fixture(scope="module")
def first_step(reference):
    """The reference's first step on one micro-batch, and how a program's
    loss and clipped first gradient compare with it (``check.leaf_gaps``)."""
    seed = 21
    toks = tokens(seed=seed)
    ref = reference.run(MODEL, OPTIM, 1.0, [toks[None]], seed)
    params = ouro.init_params(jax.random.PRNGKey(seed), config(), MIXED)

    def compare(cfg, **flags):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: looped(p, toks, cfg, MIXED, **flags)))(params)
        sq = jax.tree_util.tree_map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), grads)
        gnorm = jnp.sqrt(sum(jax.tree_util.tree_leaves(sq)))
        scale = jnp.minimum(1.0, 1.0 / (gnorm + 1e-6))
        grad1 = dict(zip(reference.leaf_names(grads),
                         (float(jnp.sqrt(s) * scale) for s in jax.tree_util.tree_leaves(sq))))
        gaps = checks.leaf_gaps(grad1, ref["grad1"])
        return {"loss_gap": abs(float(loss) - ref["loss"][0]),
                "grad1_worst_leaf": max(gaps.values())}

    return compare, params, toks


def test_the_unrolled_pieces_are_the_program(first_step):
    compare, params, toks = first_step
    cfg = config()
    whole = jax.jit(lambda p: ouro.forward(p, batch_of(toks), cfg, MIXED)[0])(params)
    pieces = jax.jit(lambda p: looped(p, toks, cfg, MIXED))(params)
    assert float(whole) == pytest.approx(float(pieces), rel=1e-6)
    sound = compare(cfg)
    assert all(sound[k] <= LIMITS[k] for k in sound), sound


@pytest.mark.parametrize("omission", sorted(OMISSIONS))
def test_an_omission_breaches_a_limit(first_step, omission):
    compare, _, _ = first_step
    spec = OMISSIONS[omission]
    broken = compare(config(**spec.get("cfg", {})), **spec.get("flags", {}))
    assert any(broken[k] > LIMITS[k] for k in broken), (omission, broken, LIMITS)


# -- what is not wired is refused by name ------------------------------------


def raw_config(**over):
    cfg = {"distributed_strategy": {"tensor_model_parallel_size": 1},
           "data": {"global_batch_size": 8, "micro_batch_size": 1, "seq_length": 64},
           "model": dict(MODEL)}
    for dotted, v in over.items():
        node = cfg
        *parents, last = dotted.split(".")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = v
    return cfg


@pytest.mark.parametrize("over, named", [
    ({"distributed_strategy.pipeline_model_parallel_size": 2}, "pipeline_model_parallel_size"),
    ({"model.total_ut_steps": 0}, "total_ut_steps"),
    ({"model.lora": {"r": 4, "alpha": 8, "target_modules": ["exit_gate"]}}, "exit_gate"),
], ids=["pipeline", "no-pass", "lora-on-the-gate"])
def test_the_loader_refuses_by_the_keys_name(over, named):
    from neuronx_distributed_training_tpu.config.loader import load_config

    assert load_config(raw_config())["model"]["architecture"] == "ouro"
    with pytest.raises(ValueError, match=named):
        load_config(raw_config(**over))


def test_the_model_refuses_with_the_loaders_words():
    # one refusal, in ``OuroConfig.from_config``; the loader calls it
    for model, ds, named in [({**MODEL, "total_ut_steps": 0}, {}, "total_ut_steps"),
                             (MODEL, {"pipeline_model_parallel_size": 2},
                              "pipeline_model_parallel_size")]:
        with pytest.raises(ValueError, match=named):
            ouro.OuroConfig.from_config(model, ds)


def test_cached_decode_and_preference_alignment_refuse_the_loop():
    with pytest.raises(NotImplementedError, match="ouro.*KV cache"):
        ouro.FAMILY.decode()
    with pytest.raises(NotImplementedError, match="OuroConfig"):
        ouro.FAMILY.logits(config(), MIXED)


# -- through nxdt-train -------------------------------------------------------


def test_trains_through_the_trainer_on_the_cpu_mesh(tmp_path):
    """``Trainer.from_config(cfg).fit()`` on tp 2 x dp 4: born-sharded
    parameters, the new columns in ``metrics.jsonl`` and the loop's facts in
    ``run_summary.json``."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(raw_config(**{
        "distributed_strategy.tensor_model_parallel_size": 2,
        "distributed_strategy.sequence_parallel": True,
        "model.optim": {"name": "adamw_fp32OptState", **OPTIM},
        "data.synthetic": True, "data.global_batch_size": 8, "data.micro_batch_size": 1,
        "trainer": {"max_steps": 3, "log_every_n_steps": 1},
        "exp_manager": {"exp_dir": str(tmp_path), "name": "ouro"},
        "precision": {"type": "mixed_precision"},
        "debug": {"validate_sharding": True},
    }))
    trainer = Trainer.from_config(cfg, enable_checkpointing=False)
    trainer.fit()
    log_dir = Path(trainer.exp.log_dir)
    rows = [json.loads(line) for line in open(log_dir / "metrics.jsonl")]
    assert [r["step"] for r in rows] == [1, 2, 3]
    for r in rows:
        assert jnp.isfinite(r["loss"])
        assert sum(r[f"exit/p_pass{t}"] for t in (1, 2, 3, 4)) == pytest.approx(1.0, abs=1e-5)
        mixed = sum(r[f"exit/p_pass{t}"] * r[f"loss/ce_pass{t}"] for t in (1, 2, 3, 4))
        assert r["loss"] == pytest.approx(mixed - 0.1 * r["exit/entropy"], abs=5e-2)
    summary = json.load(open(log_dir / "run_summary.json"))
    assert summary["loop_passes"] == 4
    assert summary["layer_applications_per_step"] == 4 * MODEL["num_layers"] * 2
    assert summary["model_family"] == "OuroConfig"
