"""Autotune planner tests: lattice legality, cost-model calibration, golden
plans, and the planner end-to-end.

The memory-calibration class is the satellite contract: the analytic
per-device HBM estimate must stay within +-15% of compiled
``memory_analysis()`` bytes (arguments + temps) on tiny configs across
dp/tp/pp/ep meshes, so the planner's OOM pruning cannot silently drift from
XLA reality.  Known exclusions (documented in docs/autotuning.md "blind
spots"): mixtral under tp>1 (strided-mesh ragged-dot workspace) and extreme
vocab/width ratios outside the tiny-config envelope.
"""

import jax
import pytest

from neuronx_distributed_training_tpu.autotune import (
    ModelFacts,
    Plan,
    enumerate_plans,
    estimate_plan,
    plan_config,
    resolve_topology,
)
from neuronx_distributed_training_tpu.autotune.cost_model import hbm_breakdown
from neuronx_distributed_training_tpu.autotune.space import REMAT_POLICIES
from neuronx_distributed_training_tpu.config.loader import load_config

EX = "examples/conf"


def tiny_raw(tp=1, pp=1, ep=1, remat="selective", gbs=8, mbs=1, seq=128,
             layers=4, h=64, ffn=176, vocab=512, heads=8, kv=4, arch="llama",
             sched=None, alignment=None, lora=False, fusions=None):
    m = {"architecture": arch, "vocab_size": vocab, "hidden_size": h,
         "intermediate_size": ffn, "num_layers": layers,
         "num_attention_heads": heads, "num_key_value_heads": kv,
         "max_position_embeddings": seq,
         "activations_checkpoint_granularity":
             None if remat == "none" else remat}
    if arch == "mixtral":
        m["moe"] = {"num_experts": 4, "top_k": 2, "dropless": True}
    if fusions:
        m["fusions"] = fusions
    if lora:
        m["lora"] = {"r": 4, "alpha": 8}
    ds = {"tensor_model_parallel_size": tp,
          "pipeline_model_parallel_size": pp,
          "expert_model_parallel_size": ep,
          "sequence_parallel": tp > 1, "zero1": True}
    if sched:
        ds["pipeline"] = {"schedule": sched}
    cfg = {"name": "tiny", "model_source": "hf", "seed": 0,
           "trainer": {"max_steps": 1},
           "distributed_strategy": ds,
           "data": {"seq_length": seq, "global_batch_size": gbs,
                    "micro_batch_size": mbs, "synthetic": True},
           "model": m, "precision": {"type": "mixed_precision"}}
    if alignment:
        cfg["model_alignment_strategy"] = alignment
    return cfg


# ---------------------------------------------------------------------------
# search space: legality properties
# ---------------------------------------------------------------------------


class TestSpaceLegality:
    @pytest.mark.parametrize("config,chips", [
        (f"{EX}/hf_llama3_8B_config.yaml", 256),
        (f"{EX}/hf_mixtral_8x7b_config.yaml", 32),
        (f"{EX}/megatron_gpt_config.yaml", 8),
        (f"{EX}/tiny_smoke_config.yaml", 8),
    ])
    def test_every_plan_is_legal(self, config, chips):
        facts = ModelFacts.from_config(load_config(config))
        plans = enumerate_plans(facts, chips)
        assert plans, f"{config} has no legal plan at {chips} chips"
        for p in plans:
            # world factorization is exact
            assert p.dp * p.tp * p.pp * p.cp == chips
            # heads shard cleanly; kv heads shard OR replicate (GQA)
            assert facts.num_heads % p.tp == 0
            assert (facts.num_kv_heads % p.tp == 0
                    or p.tp % facts.num_kv_heads == 0)
            # whole layer (or MoE+dense group) slices per stage
            if facts.moe_frequency > 1:
                assert facts.moe_groups % p.pp == 0
            else:
                assert facts.num_layers % p.pp == 0
            # experts shard over ep, ep carves dp (mesh.py contract)
            if facts.num_experts:
                assert facts.num_experts % p.ep == 0
            else:
                assert p.ep == 1
            assert p.dp % p.ep == 0
            # batch math: gbs = mbs * dp * nm exactly
            assert (facts.global_batch_size
                    == p.micro_batch_size * p.dp * p.num_microbatches)
            # cp requires a context-parallel fusion + seq divisibility
            if p.cp > 1:
                assert facts.cp_fusion is not None
                assert facts.seq % p.cp == 0
            assert p.remat in REMAT_POLICIES
            assert p.schedule == "none" if p.pp == 1 else p.schedule in (
                "1f1b", "1f1b-interleaved", "1f1b-zb", "wavefront")
            # the interleave carries the vp lattice dimension; everything
            # else runs vp == 1 (same invariants the runtime raises on)
            if p.schedule == "1f1b-interleaved":
                assert p.vp > 1
                assert p.num_microbatches >= p.pp
                if facts.moe_frequency > 1:
                    assert facts.moe_groups % (p.pp * p.vp) == 0
                else:
                    assert facts.num_layers % (p.pp * p.vp) == 0
            else:
                assert p.vp == 1

    def test_no_duplicates_and_deterministic_order(self):
        facts = ModelFacts.from_config(
            load_config(f"{EX}/hf_llama3_8B_config.yaml"))
        a = enumerate_plans(facts, 64)
        b = enumerate_plans(facts, 64)
        assert a == b, "enumeration must be deterministic"
        assert len(a) == len(set(a)), "plans must be unique"
        assert a == sorted(a, key=Plan.key), "plans must come sorted"

    def test_cp_requires_fusion(self):
        # no cp fusion configured -> no cp>1 plans, ever
        facts = ModelFacts.from_config(load_config(tiny_raw()))
        assert all(p.cp == 1 for p in enumerate_plans(facts, 8))
        # ring fusion -> cp plans appear
        facts_cp = ModelFacts.from_config(
            load_config(tiny_raw(fusions={"ring_attention": True})))
        assert any(p.cp > 1 for p in enumerate_plans(facts_cp, 8))

    def test_pp_collapses_remat(self):
        """The pipeline path ignores the remat policy (the stage loop's own
        buffering dominates — cost_model), so pp>1 plans carry exactly one
        remat value instead of three cost-identical clones."""
        facts = ModelFacts.from_config(load_config(tiny_raw()))
        plans = enumerate_plans(facts, 8)
        assert {p.remat for p in plans if p.pp > 1} == {"selective"}
        assert {p.remat for p in plans if p.pp == 1} == set(REMAT_POLICIES)


class TestScheduleGate:
    """supports_1f1b, asked with the family's answer, is the one source of
    truth the lattice honors."""

    def test_llama_gets_the_manual_vjp_family(self):
        facts = ModelFacts.from_config(load_config(tiny_raw()))
        pp_plans = [p for p in enumerate_plans(facts, 8) if p.pp > 1]
        scheds = {p.schedule for p in pp_plans}
        assert {"1f1b", "1f1b-zb", "1f1b-interleaved", "wavefront"} <= scheds

    def test_mixtral_is_wavefront_only(self):
        facts = ModelFacts.from_config(load_config(tiny_raw(arch="mixtral")))
        pp_plans = [p for p in enumerate_plans(facts, 8) if p.pp > 1]
        assert pp_plans, "mixtral should still get pp plans"
        assert {p.schedule for p in pp_plans} == {"wavefront"}

    def test_preference_alignment_is_wavefront_only(self):
        facts = ModelFacts.from_config(
            load_config(tiny_raw(alignment="orpo")))
        pp_plans = [p for p in enumerate_plans(facts, 8) if p.pp > 1]
        assert pp_plans
        assert {p.schedule for p in pp_plans} == {"wavefront"}

    def test_lora_is_wavefront_only(self):
        facts = ModelFacts.from_config(load_config(tiny_raw(lora=True)))
        pp_plans = [p for p in enumerate_plans(facts, 8) if p.pp > 1]
        assert pp_plans
        assert {p.schedule for p in pp_plans} == {"wavefront"}

    def test_zigzag_blocks_pp(self):
        facts = ModelFacts.from_config(
            load_config(tiny_raw(fusions={"zigzag_ring_attention": True})))
        assert all(p.pp == 1 for p in enumerate_plans(facts, 8))


# ---------------------------------------------------------------------------
# golden top-1 plans (representative configs; analytic ranking only)
# ---------------------------------------------------------------------------


class TestGoldenPlans:
    """Pinned winners: a cost-model change that reorders these must be a
    deliberate decision (update the snapshot in the same commit)."""

    @pytest.mark.parametrize("config,chips,topo,want", [
        # the work-compacted executor's interval-allocated chunk-input ring
        # is O(pp*vp) instead of the old lockstep O(vp*nm) store, so the
        # interleave now FITS at large nm and its smaller bubble wins the
        # same mesh (PR: cash the pipeline bubbles)
        (f"{EX}/hf_llama3_8B_config.yaml", 256, "v5e",
         Plan(tp=8, pp=4, cp=1, ep=1, dp=8, micro_batch_size=1,
              num_microbatches=128, remat="selective",
              schedule="1f1b-interleaved", vp=4)),
        # the 70B winner IS the shipped config's declared mesh layout
        (f"{EX}/hf_llama3_70B_config.yaml", 256, "v5e",
         Plan(tp=32, pp=8, cp=1, ep=1, dp=1, micro_batch_size=1,
              num_microbatches=1024, remat="selective",
              schedule="1f1b-interleaved", vp=2)),
        (f"{EX}/tiny_smoke_config.yaml", 8, "cpu",
         Plan(tp=2, pp=1, cp=1, ep=1, dp=4, micro_batch_size=2,
              num_microbatches=1, remat="none", schedule="none")),
    ])
    def test_top1(self, config, chips, topo, want):
        rep = plan_config(config, chips=chips, topology=topo, audit=False,
                          top_k=1)
        assert rep.error is None
        assert rep.candidates[0].plan == want


# ---------------------------------------------------------------------------
# cost model: structure + rank agreement helper
# ---------------------------------------------------------------------------


class TestCostModel:
    def setup_method(self):
        self.facts = ModelFacts.from_config(
            load_config(f"{EX}/hf_llama3_8B_config.yaml"))
        self.topo = resolve_topology("v5e")

    def plan(self, **kw):
        base = dict(tp=8, pp=1, cp=1, ep=1, dp=32, micro_batch_size=1,
                    num_microbatches=32, remat="selective", schedule="none")
        base.update(kw)
        return Plan(**base)

    def test_remat_trades_memory_for_compute(self):
        none = estimate_plan(self.facts, self.plan(remat="none"), self.topo)
        full = estimate_plan(self.facts, self.plan(remat="full"), self.topo)
        assert full.compute_seconds > none.compute_seconds
        assert full.hbm_breakdown["activations"] < \
            none.hbm_breakdown["activations"]

    def test_bubble_shrinks_with_microbatches(self):
        few = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="1f1b"),
            self.topo)
        many = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=128,
                                  micro_batch_size=1, schedule="1f1b"),
            self.topo)
        assert many.bubble_seconds < few.bubble_seconds

    def test_zb_bubble_strictly_below_1f1b(self):
        """ZB-H1 acceptance bar: at equal (pp, nm) the zero-bubble split's
        bubble term is strictly below plain 1f1b's (it prices only the
        warmup third the deferred wgrad tail cannot fill) — while its
        compute term is strictly above (the re-linearization forward)."""
        f1b = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="1f1b"),
            self.topo)
        zb = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="1f1b-zb"),
            self.topo)
        assert zb.bubble_seconds < f1b.bubble_seconds
        assert zb.compute_seconds > f1b.compute_seconds
        # at the multiplier level the ratio is exactly the warmup third
        from neuronx_distributed_training_tpu.parallel.pipeline import (
            bubble_multiplier,
        )

        assert bubble_multiplier("1f1b-zb", 4, 16) == pytest.approx(
            bubble_multiplier("1f1b", 4, 16) / 3.0)

    def test_wavefront_bubble_divides_by_vp(self):
        """The satellite fix: wavefront with a virtual pipeline runs the
        circular interleave (utilization nm*vp/(nm*vp + pp - 1)), so its
        bubble term divides by nm*vp — not the vp-blind (pp-1)/nm."""
        flat = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="wavefront"),
            self.topo)
        vp2 = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="wavefront",
                                  vp=2),
            self.topo)
        assert vp2.bubble_seconds == pytest.approx(flat.bubble_seconds / 2.0)

    def test_interleaved_bubble_and_ring_memory(self):
        """1f1b-interleaved divides the bubble by nm*vp and pays for it in
        chunk-input ring storage (priced as hbm_breakdown['pipeline_rings']),
        while staying far below the wavefront's per-layer residual class."""
        f1b = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="1f1b"),
            self.topo)
        il = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8,
                                  schedule="1f1b-interleaved", vp=2),
            self.topo)
        wave = estimate_plan(
            self.facts, self.plan(pp=4, dp=8, num_microbatches=16,
                                  micro_batch_size=8, schedule="wavefront",
                                  vp=2),
            self.topo)
        assert il.bubble_seconds == pytest.approx(f1b.bubble_seconds / 2.0)
        assert il.hbm_breakdown["pipeline_rings"] > 0
        assert il.hbm_bytes > f1b.hbm_bytes
        assert il.hbm_bytes < wave.hbm_bytes

    def test_wavefront_costs_more_memory_at_depth(self):
        onef1b = estimate_plan(
            self.facts, self.plan(pp=8, dp=4, num_microbatches=256,
                                  schedule="1f1b"), self.topo)
        wave = estimate_plan(
            self.facts, self.plan(pp=8, dp=4, num_microbatches=256,
                                  schedule="wavefront"), self.topo)
        assert wave.hbm_bytes > onef1b.hbm_bytes

    def test_tp_shards_memory_but_adds_comms(self):
        tp1 = estimate_plan(self.facts, self.plan(tp=1, dp=256), self.topo)
        tp8 = estimate_plan(self.facts, self.plan(tp=8, dp=32), self.topo)
        assert tp8.hbm_breakdown["params"] < tp1.hbm_breakdown["params"]
        assert tp8.comms_breakdown.get("tp", 0) > \
            tp1.comms_breakdown.get("tp", 0)


# ---------------------------------------------------------------------------
# flops breakdown: one source of truth with flops_for_model
# ---------------------------------------------------------------------------


class TestFlopsBreakdown:
    def test_gpt_with_moe_breakdown_sums_to_total(self):
        from neuronx_distributed_training_tpu.models import family, gpt
        from neuronx_distributed_training_tpu.utils import perf

        gc = gpt.GPTConfig.from_config({
            "num_layers": 4, "hidden_size": 64, "ffn_hidden_size": 176,
            "num_attention_heads": 8, "num_query_groups": 4,
            "vocab_size": 512, "activation": "swiglu",
            "moe": {"num_experts": 4, "top_k": 2},
        }, {})
        bd = family.flops_breakdown_for_model(gc, 128)
        assert set(bd) == set(perf.FLOPS_COMPONENTS)
        assert bd["router"] > 0, "MoE GPT must have a router term"
        assert sum(bd.values()) == pytest.approx(
            family.flops_for_model(gc, 128), rel=1e-12)

    def test_llama_breakdown_matches_legacy_scalar(self):
        from neuronx_distributed_training_tpu.models import family, llama
        from neuronx_distributed_training_tpu.utils import perf

        lc = llama.LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_attention_heads=32, num_kv_heads=8)
        bd = family.flops_breakdown_for_model(lc, 8192)
        legacy = perf.llama_flops_per_token(
            num_layers=32, hidden_size=4096, intermediate_size=14336,
            num_attention_heads=32, num_kv_heads=8, vocab_size=128256,
            seq_len=8192)
        assert sum(bd.values()) == pytest.approx(legacy, rel=1e-12)
        assert family.flops_for_model(lc, 8192) == pytest.approx(legacy,
                                                              rel=1e-12)

    def test_mixtral_counts_activated_experts_only(self):
        from neuronx_distributed_training_tpu.models import family, mixtral

        mc = mixtral.MixtralConfig.from_config({
            "vocab_size": 512, "hidden_size": 64, "intermediate_size": 176,
            "num_layers": 4, "num_attention_heads": 8,
            "num_key_value_heads": 4,
            "moe": {"num_experts": 8, "top_k": 2},
        }, {})
        bd = family.flops_breakdown_for_model(mc, 128)
        # 2 activated of 8 experts: the mlp term prices top_k, not E
        swiglu = 2 * 64 * 3 * 176
        assert bd["mlp"] == pytest.approx(4 * 2 * swiglu)
        assert sum(bd.values()) == pytest.approx(
            family.flops_for_model(mc, 128), rel=1e-12)


# ---------------------------------------------------------------------------
# memory-model calibration: analytic vs compiled memory_analysis()
# ---------------------------------------------------------------------------


def measured_bytes(raw, world):
    from neuronx_distributed_training_tpu.analysis.graph_audit import (
        lower_step_program,
    )
    from neuronx_distributed_training_tpu.telemetry.census import (
        memory_analysis_bytes,
    )
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    cfg = load_config(raw)
    asm = assemble_step_program(cfg, devices=jax.devices()[:world],
                                build_data=False)
    _, compiled = lower_step_program(asm)
    mem = memory_analysis_bytes(compiled)
    if mem is None:
        pytest.skip("backend has no memory_analysis()")
    return mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]


class TestMemoryCalibration:
    """The satellite contract: analytic HBM within +-15% of XLA across
    dp/tp/pp/ep meshes on tiny llama + mixtral."""

    TOLERANCE = 0.15

    @pytest.mark.parametrize("kw,world", [
        (dict(), 4),                                     # dp mesh
        (dict(layers=8), 4),                             # depth scaling
        (dict(seq=256), 4),                              # seq scaling
        (dict(remat="full"), 4),                         # remat policy
        (dict(remat="none"), 4),
        (dict(tp=2), 8),                                 # tp mesh
        (dict(tp=4), 8),
        (dict(pp=2, sched="1f1b"), 8),                   # pp mesh, 1f1b
        (dict(pp=2, sched="wavefront"), 8),              # pp mesh, wavefront
        (dict(tp=2, pp=2, sched="1f1b"), 8),             # tp x pp
        (dict(arch="mixtral"), 4),                       # moe, dense mesh
        (dict(arch="mixtral", ep=2), 8),                 # ep mesh
    ], ids=["dp", "L8", "s256", "full", "none", "tp2", "tp4", "pp2-1f1b",
            "pp2-wave", "tp2pp2", "moe", "moe-ep2"])
    def test_within_15pct(self, kw, world):
        raw = tiny_raw(**kw)
        measured = measured_bytes(raw, world)
        facts = ModelFacts.from_config(load_config(raw))
        plan = facts.declared_plan_for(world)
        assert plan is not None
        est = hbm_breakdown(facts, plan)["total"]
        ratio = est / measured
        assert abs(ratio - 1.0) <= self.TOLERANCE, (
            f"analytic {est / 1e6:.2f}M vs measured {measured / 1e6:.2f}M "
            f"(ratio {ratio:.3f}) — the cost model drifted from XLA; "
            f"recalibrate the constants in autotune/cost_model.py"
        )

    def test_state_bytes_are_exact(self):
        """Params + opt state + batch (the argument bytes) must match XLA to
        within 2% — that part is closed-form accounting, not calibration."""
        from neuronx_distributed_training_tpu.analysis.graph_audit import (
            lower_step_program,
        )
        from neuronx_distributed_training_tpu.autotune.cost_model import (
            _policy_for,
            params_per_device,
        )
        from neuronx_distributed_training_tpu.telemetry.census import (
            memory_analysis_bytes,
        )
        from neuronx_distributed_training_tpu.trainer.loop import (
            assemble_step_program,
        )

        raw = tiny_raw()
        cfg = load_config(raw)
        asm = assemble_step_program(cfg, devices=jax.devices()[:4],
                                    build_data=False)
        _, compiled = lower_step_program(asm)
        mem = memory_analysis_bytes(compiled)
        if mem is None:
            pytest.skip("backend has no memory_analysis()")
        facts = ModelFacts.from_config(cfg)
        plan = facts.declared_plan_for(4)
        bd = hbm_breakdown(facts, plan)
        policy = _policy_for(facts)
        n = params_per_device(facts, plan)
        state = bd["params"] + bd["opt_state"] + bd["batch"]
        # mixed precision: no master copy (params already f32)
        assert n > 0 and policy is not None
        assert state == pytest.approx(mem["argument_size_in_bytes"],
                                      rel=0.02)


# ---------------------------------------------------------------------------
# planner end-to-end (tiny, with the audit stage)
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_plan_config_with_audit(self):
        rep = plan_config(tiny_raw(), chips=8, topology="cpu", top_k=3,
                          max_devices=8)
        assert rep.error is None
        assert rep.n_plans > 0 and rep.candidates
        w = rep.winner
        assert w is not None, "tiny config must produce a surviving plan"
        # every surviving candidate passed the graph audit
        for c in rep.candidates:
            if not c.discarded:
                assert c.audit_verdict in ("clean", "info", "warn")
                assert c.measured_collectives is not None
                assert c.measured_memory_bytes and c.measured_memory_bytes > 0

    def test_yaml_snippet_parses_and_round_trips(self, tmp_path):
        import yaml

        from neuronx_distributed_training_tpu.autotune.planner import (
            apply_plan,
        )

        rep = plan_config(tiny_raw(), chips=8, topology="cpu", top_k=1,
                          audit=False)
        snippet = yaml.safe_load(rep.yaml_snippet())
        ds = snippet["distributed_strategy"]
        assert ds["tensor_model_parallel_size"] == rep.winner.plan.tp
        # --apply writes a loadable config with the plan imposed
        src = tmp_path / "src.yaml"
        src.write_text(yaml.safe_dump(tiny_raw()))
        dst = tmp_path / "tuned.yaml"
        apply_plan(src, dst, rep.winner.plan, rep.facts)
        tuned = load_config(dst)
        assert int(tuned["distributed_strategy"][
            "tensor_model_parallel_size"]) == rep.winner.plan.tp
        facts2 = ModelFacts.from_config(tuned)
        assert facts2.declared_plan_for(8).mesh == rep.winner.plan.mesh

    def test_unplannable_chip_count_reports_not_raises(self):
        # 7 chips: no factorization divides heads/batch -> error field set
        rep = plan_config(tiny_raw(gbs=8), chips=7, topology="cpu",
                          audit=False)
        assert rep.winner is None or rep.n_plans >= 0  # never raises

    def test_hbm_budget_prunes(self):
        # an 8B model on one cpu-profile chip (8G) cannot fit: everything
        # ranks, nothing "fits"
        rep = plan_config(f"{EX}/hf_llama3_8B_config.yaml", chips=1,
                          topology="cpu", audit=False)
        assert rep.n_fit == 0
        assert rep.candidates  # still ranked, marked unfit
        assert not rep.candidates[0].estimate.fits


# ---------------------------------------------------------------------------
# config knob block
# ---------------------------------------------------------------------------


class TestAutotuneKnobBlock:
    def test_unknown_key_dies_with_did_you_mean(self):
        raw = tiny_raw()
        raw["autotune"] = {"topk": 3}
        with pytest.raises(ValueError, match="did you mean.*top_k"):
            load_config(raw)

    def test_bad_top_k(self):
        raw = tiny_raw()
        raw["autotune"] = {"top_k": 0}
        with pytest.raises(ValueError, match="top_k"):
            load_config(raw)

    def test_bad_topology(self):
        raw = tiny_raw()
        raw["autotune"] = {"topology": "v9z"}
        with pytest.raises(ValueError, match="unknown autotune.topology"):
            load_config(raw)

    def test_bad_headroom(self):
        raw = tiny_raw()
        raw["autotune"] = {"hbm_headroom": 1.5}
        with pytest.raises(ValueError, match="hbm_headroom"):
            load_config(raw)

    def test_non_mapping_rejected(self):
        raw = tiny_raw()
        raw["autotune"] = True
        with pytest.raises(ValueError, match="autotune must be a mapping"):
            load_config(raw)

    def test_valid_block_loads(self):
        raw = tiny_raw()
        raw["autotune"] = {"enabled": True, "top_k": 3, "topology": "v5e",
                           "hbm_headroom": 0.85, "max_micro_batch_size": 4}
        cfg = load_config(raw)
        assert cfg["autotune"]["top_k"] == 3


# ---------------------------------------------------------------------------
# device identity: an unknown chip is an error, never another chip's numbers
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


class TestDeviceIdentity:
    def test_known_tpu_kinds_resolve(self):
        from neuronx_distributed_training_tpu.utils import perf

        v5e = _FakeDevice("tpu", "TPU v5 lite")
        assert perf.detect_peak_tflops(v5e) == 197.0
        assert resolve_topology(device=v5e).name == "v5e"
        assert resolve_topology(device=_FakeDevice("tpu", "TPU v4")).name == "v4"

    def test_unknown_tpu_kind_raises(self):
        from neuronx_distributed_training_tpu.utils import perf

        dev = _FakeDevice("tpu", "TPU v9 zeta")
        with pytest.raises(ValueError, match="v9 zeta"):
            perf.detect_peak_tflops(dev)
        with pytest.raises(ValueError, match="v9 zeta"):
            resolve_topology(device=dev)

    def test_cpu_has_no_peak_and_other_platforms_raise(self):
        from neuronx_distributed_training_tpu.utils import perf

        assert perf.detect_peak_tflops(jax.devices()[0]) is None
        assert resolve_topology(device=jax.devices()[0]).name == "cpu"
        with pytest.raises(ValueError, match="unrecognized"):
            resolve_topology(device=_FakeDevice("gpu", "H100"))
        with pytest.raises(ValueError, match="name or a device"):
            resolve_topology()

    def test_trainer_refuses_an_unknown_tpu(self, monkeypatch):
        """The fit loop consumes both tables; an unknown chip must stop the
        trainer, not be swallowed by the observability try-blocks."""
        from neuronx_distributed_training_tpu.trainer.loop import Trainer
        from neuronx_distributed_training_tpu.utils import perf

        def unknown(device=None):
            raise ValueError("unknown TPU device_kind 'TPU v9 zeta'")

        monkeypatch.setattr(perf, "detect_peak_tflops", unknown)
        with pytest.raises(ValueError, match="v9 zeta"):
            Trainer.from_config(load_config(tiny_raw()),
                                devices=jax.devices()[:4],
                                enable_checkpointing=False)
