"""Pre-flight graph audit: clean verdicts on shipped configs, and a seeded
violation for EVERY rule proving it fires (the fault-injection contract from
docs/static_analysis.md)."""

import dataclasses
import functools
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.analysis.graph_audit import (
    AuditContext,
    AuditReport,
    abstract_batch,
    audit_artifacts,
    audit_config,
    audit_dtypes,
    audit_step_program,
    expected_max_device_bytes,
    parse_alias_map,
    shrink_overrides,
)
from neuronx_distributed_training_tpu.config.loader import load_config
from neuronx_distributed_training_tpu.trainer.loop import assemble_step_program
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

CONF = os.path.join(os.path.dirname(__file__), "..", "examples", "conf")
TINY = os.path.join(CONF, "tiny_smoke_config.yaml")


# --------------------------------------------------------------------------
# crafted-step harness: a minimal ctx + jitted fn per fault injection
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TinyModel:
    hidden_size: int = 8
    intermediate_size: int = 8
    vocab_size: int = 8
    num_attention_heads: int = 1
    num_layers: int = 1
    max_position_embeddings: int = 8
    attention_impl: str = "flash"


def make_ctx(mesh, *, donate=True, zero1=True, policy=None, params=None,
             opt=None, pspecs=None, ospecs=None, ds_extra=None):
    params = params if params is not None else {
        "w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    opt = opt if opt is not None else {
        "m": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    ds = {"zero1": zero1, **(ds_extra or {})}
    return AuditContext(
        cfg={"distributed_strategy": ds,
             "data": {"seq_length": 8},
             "model": {}},
        mesh=mesh,
        policy=policy or DtypePolicy.from_precision_config("fp32"),
        model_cfg=TinyModel(),
        sched={"global_batch_size": 8, "micro_batch_size": 1},
        donate=donate,
        params_tree=params, opt_tree=opt, pspecs=pspecs, ospecs=ospecs,
    )


def compile_step(mesh, fn, in_specs, out_specs, args, *, donate=()):
    ns = functools.partial(NamedSharding, mesh)
    sh = lambda specs: jax.tree_util.tree_map(
        ns, specs, is_leaf=lambda x: isinstance(x, P))
    j = jax.jit(fn, in_shardings=sh(in_specs), out_shardings=sh(out_specs),
                donate_argnums=donate)
    with mesh:
        lowered = j.lower(*args)
        return lowered.as_text(), lowered.compile()


def mesh_of(devices8, shape, axes):
    import numpy as np

    return Mesh(np.asarray(devices8).reshape(shape), axes)


# --------------------------------------------------------------------------
# rule fault injections
# --------------------------------------------------------------------------


class TestRuleInjections:
    def test_ga001_donated_but_copied(self, devices8):
        """A donated buffer whose output changed dtype cannot alias."""
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            # output dtype differs from the donated input -> no alias
            return ({"w": (p["w"] + 1).astype(jnp.bfloat16)},
                    {"m": o["m"] * 2}, {"loss": b.sum()})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args, donate=(0, 1),
        )
        rep = audit_artifacts(make_ctx(mesh), comp, shlo)
        ga001 = [f for f in rep.findings if f.rule == "GA001"]
        # the bf16 output can't reuse EITHER donated f32 buffer, so exactly
        # one of the two donated inputs goes unreused (XLA picks which)
        assert len(ga001) == 1, rep.format()
        assert rep.stats["donation_coverage"] == 0.5
        assert rep.failed("error")

    def test_ga001_clean_when_aliasable(self, devices8):
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2}, {"loss": b.sum()})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args, donate=(0, 1),
        )
        rep = audit_artifacts(make_ctx(mesh), comp, shlo)
        assert not [f for f in rep.findings if f.rule == "GA001"], rep.format()
        assert rep.stats["donation_coverage"] == 1.0

    def test_ga101_dp_only_all_gather(self, devices8):
        """dp-only, zero1 off: an all-gather of params is the classic
        'replicated optimizer regathers the world' bug."""
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            # batch-sharded value forced to replicated output -> all-gather
            big = jnp.broadcast_to(b[:, None], (8, 64)) * p["w"].sum()
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2},
                    {"gathered": big})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8,), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"gathered": P()}),
            args, donate=(0, 1),
        )
        rep = audit_artifacts(make_ctx(mesh, zero1=False), comp, shlo)
        assert any(f.rule == "GA101" and "all-gather" in f.message
                   for f in rep.findings), rep.format()

    def test_ga102_tp_without_model_comms(self, devices8):
        """tp=2 mesh but a step with zero collectives: silent replication."""
        mesh = mesh_of(devices8, (4, 2), ("data", "model"))

        def step(p, o, b, k):
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2}, {"loss": b.sum(0)})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P(None), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args, donate=(0, 1),
        )
        ctx = make_ctx(mesh, ds_extra={"tensor_model_parallel_size": 2})
        rep = audit_artifacts(ctx, comp, shlo)
        rules = {f.rule for f in rep.findings}
        assert "GA102" in rules, rep.format()
        # both the tp-comms and the dp-grad-reduction contracts fire
        msgs = " | ".join(f.message for f in rep.findings)
        assert "model-axis" in msgs and "never reduced" in msgs

    def test_ga201_replicated_intermediate(self, devices8):
        """A big batch-replicated broadcast blows the per-device budget."""
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            # [8, 4096] f32 fully replicated = 128 KiB/device vs a ~KB budget
            # (a sort, so that XLA has to materialize it: a plain
            # broadcast + sum is folded into one fused reduction)
            blob = jnp.sort(jnp.sin(
                jnp.arange(8 * 4096, dtype=jnp.float32).reshape(8, 4096)
                * p["w"][0, 0]) + b.sum(), axis=-1)
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2},
                    {"loss": blob.sum()})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8,), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args, donate=(0, 1),
        )
        ctx = make_ctx(mesh)
        budget = expected_max_device_bytes(ctx)
        assert budget < 8 * 4096 * 4
        rep = audit_artifacts(ctx, comp, shlo, replication_slack=2.0)
        assert any(f.rule == "GA201" for f in rep.findings), rep.format()

    def test_ga301_f32_matmul_under_bf16(self, devices8):
        """Both-f32 dot under a bf16 regime fires; the policy's own widening
        (bf16 -> f32 convert feeding the dot) does not."""
        mesh = mesh_of(devices8, (8,), ("data",))
        bf16 = DtypePolicy.from_precision_config("mixed_precision")

        def bad(p, o, b, k):
            y = b @ p["w"]  # f32 x f32: the policy cast never happened
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2}, {"loss": y.sum()})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, bad,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args, donate=(0, 1),
        )
        rep = audit_artifacts(make_ctx(mesh, policy=bf16), comp, shlo)
        assert any(f.rule == "GA301" for f in rep.findings), rep.format()

        def promoted(p, o, b, k):
            # bf16 data widened to f32 on purpose — policy-intended
            y = b.astype(jnp.float32) @ p["w"].astype(jnp.float32)
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2}, {"loss": y.sum()})

        args_bf16 = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)},
                     {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                     jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
                     jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo2, comp2 = compile_step(
            mesh, promoted,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"loss": P()}),
            args_bf16, donate=(1,),
        )
        params_bf16 = {"w": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)}
        rep2 = audit_artifacts(
            make_ctx(mesh, policy=bf16, donate="params",
                     params=params_bf16), comp2, shlo2)
        assert not [f for f in rep2.findings if f.rule == "GA301"], \
            rep2.format()

    def test_ga301_knows_a_sum_as_product_by_its_table(self, devices8):
        """A float32 sum written as a product at ``highest`` passes where one
        operand is a table the model config lists (``sum_tables``), and only
        there: ``highest`` alone excuses nothing."""
        ctx = make_ctx(mesh_of(devices8, (8,), ("data",)),
                       policy=DtypePolicy.from_precision_config("mixed_precision"))
        ctx.model_cfg.sum_tables = lambda seq_len: ((seq_len, seq_len),)

        def f32_matmuls(side, precision="HIGHEST"):
            rep = AuditReport()
            audit_dtypes(rep, ctx, (
                f"func.func @main() {{\n  %5 = stablehlo.dot_general %3, %4, contracting_dims "
                f"= [1] x [0], precision = [{precision}, {precision}] : "
                f"(tensor<16x{side}xf32>, tensor<{side}x{side}xf32>) -> tensor<16x{side}xf32>\n}}"))
            return rep.stats["f32_matmuls"]

        assert f32_matmuls(8) == 0          # data.seq_length of make_ctx
        assert f32_matmuls(4) == 1
        assert f32_matmuls(8, "DEFAULT") == 1

    def test_ga401_bad_specs_curated(self, devices8):
        cfg = load_config(TINY, {
            "data.global_batch_size": 16,
            "data.micro_batch_size": 1,
        })
        asm = assemble_step_program(cfg, devices=devices8, build_data=False)
        asm = dataclasses.replace(
            asm, pspecs={**asm.pspecs, "embed": P("nonexistent_axis")})
        rep = audit_step_program(asm)
        ga401 = [f for f in rep.findings if f.rule == "GA401"]
        assert ga401 and "nonexistent_axis" in ga401[0].message
        assert rep.failed("error")


# --------------------------------------------------------------------------
# alias-map parsing
# --------------------------------------------------------------------------


def test_parse_alias_map_nested_braces():
    hdr = ("HloModule jit_step, is_scheduled=true, input_output_alias={ "
           "{0}: (0, {}, may-alias), {2}: (5, {}, must-alias) }, "
           "entry_computation_layout={(f32[2]{0})->f32[2]{0}}")
    assert parse_alias_map(hdr) == {0: 0, 2: 5}


def test_parse_alias_map_absent():
    assert parse_alias_map("HloModule foo, entry_computation_layout=x") == {}


# --------------------------------------------------------------------------
# config-level audits (the pre-flight CLI path)
# --------------------------------------------------------------------------


class TestConfigAudit:
    def test_tiny_smoke_clean(self):
        rep = audit_config(TINY)
        assert rep.worst() is None, rep.format()
        assert rep.stats["donation_coverage"] == 1.0

    def test_invalid_config_becomes_finding(self):
        rep = audit_config({
            "name": "bad",
            "distributed_strategy": {"sequence_parallel": True},
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "synthetic": True},
            "model": {"num_layers": 2},
        })
        assert any(f.rule == "GA000" for f in rep.findings)
        assert rep.failed("error")

    def test_shrink_preserves_structure(self):
        cfg = load_config(os.path.join(CONF, "hf_llama3_8B_config.yaml"))
        o = shrink_overrides(cfg, max_devices=8)
        assert o["distributed_strategy.tensor_model_parallel_size"] == 2
        assert o["model.num_attention_heads"] % 2 == 0
        assert o["model.hidden_size"] % o["model.num_attention_heads"] == 0
        assert o["model.vocab_size"] % 2 == 0
        # structural knobs untouched: precision / zero1 / fusions flags
        shrunk = load_config(os.path.join(CONF, "hf_llama3_8B_config.yaml"), o)
        assert shrunk.distributed_strategy.sequence_parallel \
            == cfg.distributed_strategy.sequence_parallel
        assert shrunk.get("precision") == cfg.get("precision")

    def test_abstract_batch_alignment_keys(self, devices8):
        cfg = load_config(os.path.join(CONF, "hf_llama3_8B_DPO_config.yaml"),
                          shrink_overrides(load_config(
                              os.path.join(CONF,
                                           "hf_llama3_8B_DPO_config.yaml"))))
        asm = assemble_step_program(cfg, devices=devices8[:4],
                                    build_data=False)
        batch = abstract_batch(asm)
        assert set(batch) == {
            "chosen_input_ids", "rejected_input_ids",
            "reference_chosen_logps", "reference_rejected_logps",
        }


#: every shipped example config must audit clean (acceptance criterion);
#: each lowers in ~1-2 s shrunk, so the sweep stays tier-1
@pytest.mark.parametrize(
    "config_path",
    sorted(glob.glob(os.path.join(CONF, "*.yaml"))),
    ids=lambda p: os.path.basename(p).replace("_config.yaml", ""),
)
def test_example_config_audits_clean(config_path):
    rep = audit_config(config_path)
    assert rep.worst() is None, rep.format()
    assert rep.stats.get("donation_coverage") == 1.0, rep.format()


# --------------------------------------------------------------------------
# in-loop wiring: telemetry.graph_audit audits the census executable
# --------------------------------------------------------------------------


def test_trainer_graph_audit_in_run_summary(tmp_path):
    import json

    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(TINY, {
        "exp_manager.exp_dir": str(tmp_path),
        "exp_manager.telemetry.graph_audit": True,
        "data.global_batch_size": 16,
        "data.micro_batch_size": 1,
        "trainer.max_steps": 2,
    })
    trainer = Trainer.from_config(cfg, enable_checkpointing=False)
    trainer.fit()
    with open(os.path.join(trainer.exp.log_dir, "run_summary.json")) as f:
        summary = json.load(f)
    assert "graph_audit" in summary
    assert summary["graph_audit"]["verdict"] == "clean"
    assert summary["graph_audit"]["stats"]["donation_coverage"] == 1.0


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def test_preflight_cli_main(monkeypatch, capsys):
    import sys

    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    sys.path.insert(0, tools)
    try:
        import preflight_audit

        monkeypatch.setattr(sys, "argv", [
            "preflight_audit.py", "--config", TINY, "--lint"])
        with pytest.raises(SystemExit) as exc:
            preflight_audit.main()
        assert exc.value.code == 0
        out = capsys.readouterr().out
        assert "clean" in out and "jaxlint" in out
    finally:
        sys.path.remove(tools)
