import pytest

from neuronx_distributed_training_tpu.config.loader import (
    batch_schedule,
    load_config,
    validate_config,
)

REFERENCE_STYLE_YAML = """
name: hf_llama
model_source: hf
seed: 1234

trainer:
  max_steps: 100
  log_every_n_steps: 10
  gradient_clip_val: 1.0

exp_manager:
  exp_dir: /tmp/exp
  resume_if_exists: True
  checkpoint_callback_params:
    save_top_k: 1
    every_n_train_steps: 10
    model_parallel_size: ${multiply:${distributed_strategy.tensor_model_parallel_size}, ${distributed_strategy.pipeline_model_parallel_size}}

distributed_strategy:
  tensor_model_parallel_size: 4
  pipeline_model_parallel_size: 2
  zero1: True
  sequence_parallel: True

data:
  micro_batch_size: 1
  global_batch_size: 8

model:
  num_layers: 4
  hidden_size: 64
  optim:
    name: adamw_fp32OptState
    lr: 1.5e-4
    sched:
      name: LinearAnnealingWithWarmUp
      warmup_steps: 10
      max_steps: ${trainer.max_steps}

precision:
  type: mixed_precision

compiler_flags: '--model-type transformer'
neuron_rt_exec_timeout: 100
"""


@pytest.fixture()
def cfg(tmp_path):
    p = tmp_path / "conf.yaml"
    p.write_text(REFERENCE_STYLE_YAML)
    return load_config(p)


def test_interpolation(cfg):
    assert cfg.exp_manager.checkpoint_callback_params.model_parallel_size == 8
    assert cfg.model.optim.sched.max_steps == 100


def test_attr_and_path_access(cfg):
    assert cfg.distributed_strategy.tensor_model_parallel_size == 4
    assert cfg.get_path("model.optim.lr") == 1.5e-4
    assert cfg.get_path("model.not.there", "dflt") == "dflt"


def test_neuron_keys_tolerated(cfg):
    # Neuron-only knobs accepted without error
    assert cfg.compiler_flags == "--model-type transformer"


def test_batch_schedule(cfg):
    # world 16: dp = 16/(4*2) = 2; num_micro = 8/(1*2) = 4  (reference base.py:54-57)
    sched = batch_schedule(cfg, 16)
    assert sched == {
        "dp_size": 2,
        "num_microbatches": 4,
        "micro_batch_size": 1,
        "global_batch_size": 8,
    }


def test_validation_rejects_bad_configs():
    with pytest.raises(ValueError):
        load_config(
            {
                "distributed_strategy": {"sequence_parallel": True, "tensor_model_parallel_size": 1},
            }
        )
    with pytest.raises(ValueError):
        load_config(
            {
                "distributed_strategy": {
                    "pipeline_model_parallel_size": 2,
                    "virtual_pipeline_model_parallel_size": 2,
                },
                "model": {"num_layers": 6},
            }
        )
    with pytest.raises(ValueError):
        load_config({"model": {"moe": {"dropless": True, "capacity_factor": 2.0}}})


def test_overrides(tmp_path):
    p = tmp_path / "conf.yaml"
    p.write_text(REFERENCE_STYLE_YAML)
    cfg = load_config(p, overrides={"model.num_layers": 2, "trainer.max_steps": 5})
    assert cfg.model.num_layers == 2
    assert cfg.model.optim.sched.max_steps == 5


def test_all_shipped_configs_load_and_build():
    """Every examples/conf YAML must load through the reference-schema loader
    and produce a valid model config + batch schedule (catches key drift)."""
    import glob

    from neuronx_distributed_training_tpu.config.loader import (
        batch_schedule,
        load_config,
    )
    from neuronx_distributed_training_tpu.models.family import resolve

    configs = sorted(glob.glob("examples/conf/*.yaml"))
    assert len(configs) >= 20  # parity-class config pack
    for path in configs:
        cfg = load_config(path)
        family, model_cfg = resolve(cfg)
        assert model_cfg.num_layers > 0, path
        ds = dict(cfg.get("distributed_strategy", {}) or {})
        n_needed = (int(ds.get("tensor_model_parallel_size", 1))
                    * int(ds.get("pipeline_model_parallel_size", 1))
                    * int(ds.get("context_parallel_size", 1)))
        sched = batch_schedule(cfg, n_needed)
        assert sched["num_microbatches"] >= 1, path
        # specs build without touching devices
        specs = family.param_specs(model_cfg)
        assert "layers" in specs, path


class TestValidationCatalog:
    """The central unsupported-combination catalog (reference
    megatron_base_model.py:71-129) — every rejection carries a curated,
    actionable message and fires at load time, before any compilation."""

    def _base(self, **over):
        cfg = {
            "distributed_strategy": {"tensor_model_parallel_size": 1},
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 64},
            "model": {"num_layers": 4, "num_attention_heads": 4},
        }
        for dotted, v in over.items():
            cur = cfg
            parts = dotted.split(".")
            for p in parts[:-1]:
                cur = cur.setdefault(p, {})
            cur[parts[-1]] = v
        return cfg

    def _expect(self, match, **over):
        with pytest.raises(ValueError, match=match):
            load_config(self._base(**over))

    def test_sp_without_tp(self):
        self._expect("sequence_parallel requires",
                     **{"distributed_strategy.sequence_parallel": True})

    def test_vp_without_pp(self):
        self._expect("virtual pipeline requires",
                     **{"distributed_strategy.virtual_pipeline_model_parallel_size": 2})

    def test_layers_not_divisible_by_pp_vp(self):
        self._expect("divide evenly into pp",
                     **{"distributed_strategy.pipeline_model_parallel_size": 3})

    def test_gbs_not_divisible_by_mbs(self):
        self._expect("not divisible by micro_batch_size",
                     **{"data.micro_batch_size": 3})

    def test_moe_groups_vs_pp_vp(self):
        self._expect("MoE\\+dense groups",
                     **{"model.moe.moe_frequency": 2, "model.num_layers": 4,
                        "distributed_strategy.pipeline_model_parallel_size": 4,
                        "model.fusions.ring_attention": True})

    def test_moe_frequency_must_divide_layers(self):
        self._expect("multiple of\\s+moe.moe_frequency",
                     **{"model.moe.moe_frequency": 3, "model.num_layers": 4})

    def test_cp_without_cp_aware_attention(self):
        self._expect("context-parallel attention",
                     **{"distributed_strategy.context_parallel_size": 2,
                        "model.fusions.flash_attention": True})

    def test_cp_seq_divisibility(self):
        self._expect("divisible by\\s+context_parallel_size",
                     **{"distributed_strategy.context_parallel_size": 4,
                        "model.fusions.ring_attention": True,
                        "data.seq_length": 30})

    def test_zigzag_under_pp(self):
        self._expect("zigzag_ring_attention is not supported under pipeline",
                     **{"model.fusions.zigzag_ring_attention": True,
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "model.num_layers": 4})

    def test_zigzag_with_sliding_window(self):
        self._expect("does not support sliding_window",
                     **{"model.fusions.zigzag_ring_attention": True,
                        "model.sliding_window": 1024})

    def test_zigzag_seq_two_cp(self):
        self._expect("divisible\\s+by 2\\*context_parallel_size",
                     **{"model.fusions.zigzag_ring_attention": True,
                        "distributed_strategy.context_parallel_size": 2,
                        "data.seq_length": 34})

    def test_ulysses_head_budget(self):
        self._expect("head budget",
                     **{"model.fusions.ulysses_attention": True,
                        "distributed_strategy.context_parallel_size": 8,
                        "model.num_attention_heads": 4})

    def test_unknown_precision_regime(self):
        self._expect("unknown precision.type",
                     **{"precision.type": "fp8_who_knows"})

    def test_two_alignment_strategies(self):
        self._expect("exactly one",
                     **{"model_alignment_strategy.dpo.beta": 0.1,
                        "model_alignment_strategy.kto.beta": 0.1})

    def test_moe_dropless_capacity_conflict(self):
        self._expect("dropless",
                     **{"model.moe.dropless": True,
                        "model.moe.capacity_factor": 1.5})

    def test_unknown_block_type(self):
        self._expect("transformer_block_type",
                     **{"model.transformer_block_type": "sandwich"})

    def test_normformer_moe_conflict(self):
        self._expect("dense-only",
                     **{"model.transformer_block_type": "normformer",
                        "model.moe.num_experts": 4})

    def test_typod_alignment_string(self):
        self._expect("unknown model_alignment_strategy",
                     **{"model_alignment_strategy": "dp0"})

    def test_alignment_block_without_known_name(self):
        self._expect("names none",
                     **{"model_alignment_strategy.ppo.beta": 0.1})

    def test_nested_alignment_rejected(self):
        self._expect("config ROOT",
                     **{"model.model_alignment_strategy": "dpo"})

    def test_segment_mask_under_cp_rejected(self):
        self._expect("segment_mask",
                     **{"model_alignment_strategy.sft.segment_mask": True,
                        "distributed_strategy.context_parallel_size": 2,
                        "model.fusions.ring_attention": True})

    def test_segment_mask_with_cp_fusion_rejected(self):
        # cp == 1 but a CP fusion enabled still trips the trace-time path
        self._expect("segment_mask",
                     **{"model_alignment_strategy.sft.segment_mask": True,
                        "model.fusions.ulysses_attention": True})

    def test_segment_mask_flash_only_passes(self):
        load_config(self._base(
            **{"model_alignment_strategy.sft.segment_mask": True,
               "model_alignment_strategy.sft.packing": True,
               "model.fusions.flash_attention": True}))

    def test_blockwise_cp_under_pp_nonsmooth_seq_rejected(self):
        # prime-ish seq len under CP x PP would degrade the blockwise body to
        # a tiny kv block and an s-step scan — must die at load time
        self._expect("smoother length",
                     **{"distributed_strategy.context_parallel_size": 2,
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "model.fusions.ring_attention": True,
                        "model.num_layers": 4,
                        "data.seq_length": 2 * 1019})  # 2038 = 2 x prime

    def test_blockwise_cp_under_pp_smooth_seq_passes(self):
        load_config(self._base(
            **{"distributed_strategy.context_parallel_size": 2,
               "distributed_strategy.pipeline_model_parallel_size": 2,
               "model.fusions.ring_attention": True,
               "model.num_layers": 4,
               "data.seq_length": 2048}))



class TestPipelineScheduleKnob:
    """distributed_strategy.pipeline.schedule validation (the 1F1B knob)."""

    _base = TestValidationCatalog._base
    _expect = TestValidationCatalog._expect

    def test_unknown_schedule_rejected(self):
        self._expect("pipeline.schedule",
                     **{"distributed_strategy.pipeline.schedule": "gpipe",
                        "distributed_strategy.pipeline_model_parallel_size": 2})

    def test_unknown_pipeline_key_rejected(self):
        self._expect("unknown distributed_strategy.pipeline keys",
                     **{"distributed_strategy.pipeline.shedule": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2})

    def test_1f1b_requires_pp(self):
        self._expect("requires",
                     **{"distributed_strategy.pipeline.schedule": "1f1b"})

    def test_1f1b_rejects_vp(self):
        self._expect("virtual",
                     **{"distributed_strategy.pipeline.schedule": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "distributed_strategy.virtual_pipeline_model_parallel_size": 2,
                        "model.num_layers": 4})

    def test_1f1b_rejects_cp(self):
        self._expect("context parallelism",
                     **{"distributed_strategy.pipeline.schedule": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "distributed_strategy.context_parallel_size": 2,
                        "model.fusions.ring_attention": True,
                        "data.seq_length": 1024})

    def test_1f1b_rejects_preference_alignment(self):
        self._expect("token-level CE",
                     **{"distributed_strategy.pipeline.schedule": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "model_alignment_strategy": "dpo"})

    def test_1f1b_rejects_lora(self):
        self._expect("LoRA",
                     **{"distributed_strategy.pipeline.schedule": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "model.lora.r": 8})

    def test_valid_schedules_load(self):
        for sched in ("auto", "1f1b", "1f1b-zb", "wavefront"):
            load_config(self._base(
                **{"distributed_strategy.pipeline.schedule": sched,
                   "distributed_strategy.pipeline_model_parallel_size": 2}))

    def test_interleaved_loads_with_vp(self):
        load_config(self._base(
            **{"distributed_strategy.pipeline.schedule": "1f1b-interleaved",
               "distributed_strategy.pipeline_model_parallel_size": 2,
               "distributed_strategy.virtual_pipeline_model_parallel_size": 2,
               "model.num_layers": 4}))

    def test_interleaved_rejects_vp1(self):
        self._expect("nothing to interleave",
                     **{"distributed_strategy.pipeline.schedule":
                        "1f1b-interleaved",
                        "distributed_strategy.pipeline_model_parallel_size": 2})

    def test_zb_rejects_vp(self):
        self._expect("1f1b-interleaved",
                     **{"distributed_strategy.pipeline.schedule": "1f1b-zb",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "distributed_strategy."
                        "virtual_pipeline_model_parallel_size": 2,
                        "model.num_layers": 4})

    def test_zb_rejects_cp(self):
        self._expect("context parallelism",
                     **{"distributed_strategy.pipeline.schedule": "1f1b-zb",
                        "distributed_strategy.pipeline_model_parallel_size": 2,
                        "distributed_strategy.context_parallel_size": 2,
                        "model.fusions.ring_attention": True,
                        "data.seq_length": 1024})


class TestUnknownKnobRejection:
    """Every validated knob block rejects unknown keys with a did-you-mean
    hint — a typo'd knob must die at load, corrected, not silently run with
    defaults."""

    _base = TestValidationCatalog._base
    _expect = TestValidationCatalog._expect

    def test_pipeline_typo_hint(self):
        self._expect(r"did you mean: 'schedul' -> 'schedule'",
                     **{"distributed_strategy.pipeline.schedul": "1f1b",
                        "distributed_strategy.pipeline_model_parallel_size": 2})

    def test_pipeline_non_mapping_block(self):
        self._expect("distributed_strategy.pipeline must be a mapping",
                     **{"distributed_strategy.pipeline": "1f1b"})

    def test_pipeline_unknown_without_close_match(self):
        # far-off keys still rejected, just without a suggestion
        self._expect("unknown distributed_strategy.pipeline keys",
                     **{"distributed_strategy.pipeline.zzz": 1,
                        "distributed_strategy.pipeline_model_parallel_size": 2})

    def test_telemetry_typo_hint(self):
        self._expect(r"did you mean: 'spanss' -> 'spans'",
                     **{"exp_manager.telemetry.spanss": True})

    def test_telemetry_non_mapping_block(self):
        self._expect("exp_manager.telemetry must be a mapping",
                     **{"exp_manager.telemetry": [1, 2]})

    def test_telemetry_non_bool_knob(self):
        self._expect("must be a boolean",
                     **{"exp_manager.telemetry.mfu": "yes"})

    def test_health_typo_hint(self):
        self._expect(r"did you mean: 'polcy' -> 'policy'",
                     **{"exp_manager.telemetry.health.polcy": "halt"})

    def test_health_unknown_policy_value(self):
        self._expect("policy must be one of",
                     **{"exp_manager.telemetry.health.policy": "explode"})

    def test_health_non_mapping_block(self):
        self._expect("telemetry.health must be a mapping",
                     **{"exp_manager.telemetry.health": [1]})

    def test_graph_audit_knob_accepted(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        cfg = load_config(self._base(
            **{"exp_manager.telemetry.graph_audit": True}))
        from neuronx_distributed_training_tpu.telemetry import TelemetryConfig

        tc = TelemetryConfig.from_config(cfg.exp_manager.telemetry)
        assert tc.graph_audit is True
