"""The training loop — `train(cfg)` replaces the reference's L1/L2 stack.

Where the reference assembles NLPTrainer + NLPDDPStrategy + Lightning fit loops
+ exp_manager (reference ``examples/training.py:41-94``,
``nlp_overrides.py:288-533``), this is one explicit loop:

    cfg -> mesh, dtype policy, model, data module, optimizer, checkpointer
    for step in range(max_steps):
        batch -> sharded device arrays -> jitted train step -> metrics
        periodic: validation, checkpoint (async), logging

Auto-resume restores params/opt-state/step/consumed-samples from the newest
checkpoint (the reference's ``resume_if_exists`` flow, ``exp_manager.py:333-404``).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_training_tpu.telemetry import recompile as _recompile
from neuronx_distributed_training_tpu.telemetry.spans import (
    claim_startup,
    compile_sections,
    named,
    startup_add,
    startup_phase,
)

# the module's imports are a phase of the start-up timeline (orbax is not
# among them: the first ``Checkpointer`` loads it, checkpoint/manager.py);
# under ``nxdt-train`` the CLI's bracket is open around this one, which then
# counts there
with startup_phase("startup/imports"):
    from neuronx_distributed_training_tpu.checkpoint import (
        CheckpointConfig,
        Checkpointer,
        TrainState,
    )
    from neuronx_distributed_training_tpu.config.loader import ConfigDict, batch_schedule
    from neuronx_distributed_training_tpu.data import (
        DataModule,
        DataStallError,
        PrefetchIterator,
        SyntheticDataModule,
    )
    from neuronx_distributed_training_tpu.models.family import flops_for_model, resolve
    from neuronx_distributed_training_tpu.optim.adamw import (
        AdamWConfig,
        EMAConfig,
        init_opt_state,
        opt_state_specs,
    )
    from neuronx_distributed_training_tpu.optim.lr import build_lr_schedule
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from neuronx_distributed_training_tpu.trainer.exp_manager import ExpManager
    from neuronx_distributed_training_tpu.trainer.step import (
        jit_train_step,
        make_eval_step,
        make_train_step,
    )
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

logger = logging.getLogger(__name__)

#: base seed of the loop's per-step RNG derivation — each train step runs
#: with ``fold_in(PRNGKey(STEP_KEY_SEED), step)``, and the flight recorder's
#: bundles cite the same recipe for offline replay (one source of truth)
STEP_KEY_SEED = 0


def parse_max_time(value: Any) -> Optional[float]:
    """``trainer.max_time`` -> seconds.  Accepts NeMo's ``DD:HH:MM:SS`` string
    (reference ``StatelessTimer``, ``examples/training.py:65-69``) or a number
    of seconds.  "Stateless": each (re)start gets the full budget — elapsed
    time is deliberately NOT carried through checkpoints, so a requeued SLURM
    job trains for another ``max_time`` instead of exiting immediately."""
    if value in (None, "", 0):
        return None
    if isinstance(value, (int, float)):
        return float(value)
    parts = [int(p) for p in str(value).split(":")]
    if len(parts) != 4:
        raise ValueError(f"trainer.max_time must be DD:HH:MM:SS, got {value!r}")
    d, h, m, s = parts
    return float(((d * 24 + h) * 60 + m) * 60 + s)


def _local_mesh_devices(mesh) -> list:
    """This process's devices of the mesh (every device single-host)."""
    devices = list(getattr(mesh, "local_devices", None) or mesh.devices.flat)
    if not devices:
        devices = list(mesh.devices.flat)
    return devices


#: ``memory/`` metric -> its legacy ``device_*`` key (telemetry.
#: device_memory predates the memory plane; beacons and dashboards key on
#: these names)
_LEGACY_DEVICE_MEMORY_KEYS = (
    ("memory/bytes_in_use_max", "device_bytes_in_use"),
    ("memory/peak_bytes_max", "device_peak_bytes_in_use"),
    ("memory/bytes_limit_min", "device_bytes_limit"),
    ("memory/bytes_in_use_min", "device_bytes_in_use_min"),
    ("memory/bytes_in_use_p50", "device_bytes_in_use_p50"),
    ("memory/peak_device", "device_peak_device"),
)


def _legacy_device_memory_keys(mm: dict[str, float]) -> dict[str, float]:
    """``memory/`` metrics -> the legacy ``device_*`` names, so a boundary
    with BOTH ``device_memory`` and ``telemetry.memory`` on runs ONE
    allocator sweep (the two keys would otherwise come from two sweeps at
    slightly different instants and disagree within one record)."""
    return {dst: mm[src] for src, dst in _LEGACY_DEVICE_MEMORY_KEYS
            if src in mm}


def _device_memory_metrics(mesh) -> dict[str, float]:
    """Live allocator stats across ALL local mesh devices
    (telemetry.device_memory).

    ``memory_stats()`` is a local allocator query — no device sync — but
    some backends (CPU, older plugins) don't implement it; those log
    nothing.  The legacy ``device_*`` keys carry the WORST device (max
    in-use/peak, min limit) with min/p50 spread alongside and the peak
    device named by index — a skewed-stage pp run must not hide an
    OOM-bound device behind a roomy rank 0."""
    from neuronx_distributed_training_tpu.telemetry.memory import (
        device_memory_samples,
        memory_metrics,
    )

    samples = device_memory_samples(_local_mesh_devices(mesh))
    return _legacy_device_memory_keys(memory_metrics(samples))


def _sidecar_load(path, tag):
    """Read a reference-logp sidecar -> (done_upto, cols) or None.

    URI paths (gs://) read through epath; local reads tolerate a truncated
    file (crash mid-write predating the atomic spill) by recomputing."""
    if path is None:
        return None
    try:
        if "://" in str(path):
            import io

            from etils import epath

            p = epath.Path(path)
            if not p.exists():
                return None
            loaded = np.load(io.BytesIO(p.read_bytes()))
        else:
            import os

            if not os.path.exists(path):
                return None
            loaded = np.load(path)
    except Exception:
        logger.warning("%s sidecar %s unreadable; recomputing", tag, path)
        return None
    files = [k for k in loaded.files if k != "_done_upto"]
    done = int(loaded["_done_upto"]) if "_done_upto" in loaded.files else (
        len(loaded[files[0]]) if files else 0)
    return done, {k: np.array(loaded[k]) for k in files}


def _sidecar_store(path, done, cols):
    """Write the sidecar atomically: local tmp + rename, or a single remote
    object write (object stores commit whole objects)."""
    if "://" in str(path):
        import io

        from etils import epath

        buf = io.BytesIO()
        np.savez(buf, _done_upto=done, **cols)
        p = epath.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(buf.getvalue())
        return
    import os

    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, _done_upto=done, **cols)
    os.replace(tmp, path)


@dataclasses.dataclass
class StepProgram:
    """The config's train step as a PROGRAM, before any device state exists.

    Everything ``Trainer.from_config`` derives purely from the config — mesh,
    dtype policy, model, loss, specs, the jitted (but un-lowered) train step,
    abstract param/opt trees — with zero arrays materialized and no data files
    opened.  Two consumers:

    - ``Trainer.from_config`` materializes it (sharded-at-birth init, data
      modules, checkpointing) into a live session;
    - ``analysis.graph_audit`` AOT-lowers it on abstract inputs and checks the
      compiled artifact against the config's declared contracts (donation,
      collective census, precision) without spending a device-hour.

    ``build_data=False`` (the audit path) skips ``build_data_module`` entirely:
    no tokenizer download, no arrow/mmap open — ``shift_labels`` is derived
    statically (Megatron mmap data, the only pre-shifted source, is keyed on
    ``data.data_prefix``) and both data modules stay ``None``.
    """

    cfg: ConfigDict
    mesh: Any
    mesh_cfg: Any
    policy: DtypePolicy
    sched: dict
    seed: int
    alignment: str
    align_params: dict
    model_cfg: Any
    loss_fn: Callable
    eval_loss_fn: Callable
    forward_logits: Optional[Callable]
    param_builder: Callable
    init_key: Any
    abstract_params: Any
    pspecs: Any
    ospecs: Any
    opt_cfg: Any
    ema_cfg: Optional[Any]
    health_cfg: Any
    tensorstats_cfg: Any
    tensorstats_bucket_groups: tuple
    trainable: Any
    lora_block: dict
    jstep: Callable
    eval_fn: Optional[Callable]
    data_module: Optional[DataModule]
    val_data_module: Optional[DataModule]
    shift_labels: bool
    pipeline_schedule: Optional[str]
    num_micro_in_step: int
    max_steps: int
    donate: Any


@dataclasses.dataclass
class Trainer:
    """Assembled training session.  Build with ``Trainer.from_config``."""

    cfg: ConfigDict
    mesh: Any
    policy: DtypePolicy
    model_cfg: Any
    loss_fn: Callable
    params: Any
    opt_state: Any
    param_specs: Any
    opt_specs: Any
    train_step: Callable
    eval_step: Optional[Callable]
    data_module: DataModule
    val_data_module: Optional[DataModule]
    exp: ExpManager
    checkpointer: Optional[Checkpointer]
    max_steps: int
    step: int = 0
    pre_fit: Optional[Callable] = None  # runs once before the loop (DPO ref pass)
    ema_cfg: Optional[Any] = None  # optim.adamw.EMAConfig when EMA is enabled
    # resolved schedule under pp ("1f1b"/"1f1b-interleaved"/"1f1b-zb"/
    # "wavefront"), else None
    pipeline_schedule: Optional[str] = None
    # static facts of the run (model family, chips, seq len, analytic FLOPs)
    # persisted with the compile census into run_summary.json
    run_facts: dict = dataclasses.field(default_factory=dict)
    # donation mode the jitted step was built with (StepProgram.donate) —
    # the in-loop graph audit checks the SAME donated set, not a re-derived one
    donate: Any = True
    # elastic-resume policy (trainer.elastic.ElasticConfig; parsed from
    # exp_manager.elastic): SIGTERM grace window, save retry, replan knobs
    elastic: Optional[Any] = None
    # restart-time replan record (trainer.elastic.maybe_replan) — set by the
    # CLI / drill harness when the live world size differed from the
    # checkpoint manifest; fit() accounts its wall time as a "replan" span
    # and persists it in run_summary.json's elastic section
    replan_record: Optional[dict] = None
    # integrity trail of the DISCOVERY-time verification (trainer.elastic.
    # maybe_replan walked back / quarantined before this trainer existed);
    # merged with the checkpointer's own restore trail into the
    # run_summary.json integrity section at teardown
    discovery_integrity_trail: Optional[dict] = None
    # preemption drill hook (trainer.elastic.FaultInjector): fires at the
    # step/save/restore injection points; None outside drills
    fault_injector: Optional[Any] = None
    # sigterm-mode injection at the save/restore points happens outside the
    # fit loop's scope, so those call sites park the notice here and the loop
    # top converts it into a graceful-stop request (same path as SIGTERM)
    preemption_notice: Optional[str] = None
    # drill/test seam of the fleet control plane (trainer.control): extra
    # control-word bits standing in for other hosts' contributions on a
    # single-process mesh; the production path folds real processes through
    # the boundary collective
    control_peer_words: Optional[Callable[[], int]] = None
    # the deciding stop condition of the finished run ("health_halt",
    # "alert_halt", "data_stall", "preemption", "operator_stop",
    # "max_time"; None for a clean completion) — trainer.control's
    # exit_code_for_stop maps it to the orchestrator-facing exit code
    stop_class: Optional[str] = None

    # -- assembly -----------------------------------------------------------

    @classmethod
    def from_config(
        cls,
        cfg: ConfigDict,
        *,
        data_module: Optional[DataModule] = None,
        val_data_module: Optional[DataModule] = None,
        devices: Optional[list] = None,
        enable_checkpointing: bool = True,
    ) -> "Trainer":
        # each stretch below is a phase of the process's start-up timeline
        # (telemetry/spans.py::STARTUP; host wall time, nothing syncs)
        if devices is None:
            with startup_phase("startup/backend"):
                devices = jax.devices()
        with startup_phase("startup/assemble"):
            asm = cls.assemble(
                cfg, devices=devices, data_module=data_module,
                val_data_module=val_data_module,
            )
        return cls._materialize(
            asm, devices=devices, enable_checkpointing=enable_checkpointing
        )

    @staticmethod
    def assemble(
        cfg: ConfigDict,
        *,
        devices: Optional[list] = None,
        data_module: Optional[DataModule] = None,
        val_data_module: Optional[DataModule] = None,
        build_data: bool = True,
    ) -> StepProgram:
        """Derive the config's :class:`StepProgram` — everything up to (and
        including) the jitted train step — with zero arrays materialized.

        ``build_data=False`` (the graph-audit path) additionally skips the
        data-module build: no tokenizer fetch, no arrow/mmap open.
        ``shift_labels`` is then derived statically — the Megatron mmap
        module (keyed on ``data.data_prefix``, pretraining only) is the one
        pre-shifted source the dispatch can produce."""
        devices = devices if devices is not None else jax.devices()
        mesh_cfg = MeshConfig.from_config(cfg.get("distributed_strategy", {}))
        mesh = build_mesh(mesh_cfg, devices=devices)
        # engineered compute/comms overlap knobs (optim.overlap): bucketed
        # ZeRO-1 collectives + double-buffered pipeline hops, both opt-in
        from neuronx_distributed_training_tpu.optim.overlap import (
            OverlapConfig,
            build_bucket_plan,
        )

        overlap_cfg = OverlapConfig.from_config(
            (cfg.get("distributed_strategy", {}) or {}).get("overlap")
        )
        policy = DtypePolicy.from_precision_config(cfg.get("precision", {}))
        sched = batch_schedule(cfg, len(devices))
        seed = int(cfg.get("seed", 1234))

        # data first: the module's label convention decides shift_labels
        # (reference training.py:71-91 selects the DataModule the same way)
        from neuronx_distributed_training_tpu.data.build import (
            alignment_strategy,
            build_data_module,
        )

        alignment, align_params = alignment_strategy(cfg)
        if build_data:
            if data_module is None:
                data_module, cfg_val_dm = build_data_module(cfg, sched, seed=seed)
                if val_data_module is None:
                    val_data_module = cfg_val_dm
            # Megatron mmap data is pre-shifted on host (gpt_dataset_patch
            # convention); everything else relies on the in-model shift
            shift_labels = not getattr(data_module, "labels_pre_shifted", False)
        else:
            shift_labels = not (
                not alignment
                and (cfg.get("data", {}) or {}).get("data_prefix")
            )

        family, model_cfg = resolve(cfg)
        loss_fn = family.loss(model_cfg, policy, shift_labels=shift_labels)
        specs_fn = lambda **kw: family.param_specs(model_cfg, **kw)
        # params are NOT materialized here: param_builder composes init +
        # LoRA + pipeline-interleave as one pure function, jitted later with
        # out_shardings so every leaf is born sharded on its own devices —
        # the TPU-native form of the reference's meta-device init +
        # sequential_move_factor staged moves (base.py:147-152, 693-712);
        # a 405B-class config never materializes unsharded params anywhere
        init_key = jax.random.PRNGKey(seed)
        param_builder = lambda key: family.init_params(key, model_cfg, policy)

        # DPO/ORPO swap the loss for the preference objective; DPO's pre-fit
        # reference-logprob pass runs in fit() (reference base_dpo.py:23-66),
        # ORPO needs no reference model (reference base_orpo.py:26-46)
        forward_logits = None
        if alignment in ("dpo", "orpo", "kto"):
            dpo_cfg = dict((cfg.get("model", {}) or {}).get(alignment, {}) or {})
            forward_logits = family.logits(model_cfg, policy)

            # reference spells it kl_beta in the strategy block
            beta = float(align_params.get("kl_beta", dpo_cfg.get("beta", 0.1)))
            if alignment == "dpo":
                from neuronx_distributed_training_tpu.alignment.dpo import make_dpo_loss_fn

                loss_fn = make_dpo_loss_fn(forward_logits, beta=beta)
            elif alignment == "kto":
                # unpaired preference (extension; see alignment/kto.py)
                from neuronx_distributed_training_tpu.alignment.kto import make_kto_loss_fn

                loss_fn = make_kto_loss_fn(
                    forward_logits, beta=beta,
                    desirable_weight=float(
                        align_params.get("desirable_weight", 1.0)),
                    undesirable_weight=float(
                        align_params.get("undesirable_weight", 1.0)),
                    kl_estimator=str(
                        align_params.get("kl_estimator", "batch_mean")),
                )
            else:
                from neuronx_distributed_training_tpu.alignment.orpo import make_orpo_loss_fn

                loss_fn = make_orpo_loss_fn(forward_logits, beta=beta)

        # LoRA: inject adapters + freeze base weights (reference
        # llama_model.py:51-65 -> nxd lora_config)
        trainable = None
        lora_block = dict((cfg.get("model", {}) or {}).get("lora", {}) or {})
        if lora_block:
            from neuronx_distributed_training_tpu.peft import (
                LoraConfig as _LoraConfig,
                add_lora,
                lora_param_specs,
                trainable_mask,
            )

            lora_cfg = _LoraConfig.from_config(lora_block)
            lora_key = jax.random.PRNGKey(seed + 1)
            base_builder = param_builder
            param_builder = lambda key: add_lora(base_builder(key), lora_cfg, lora_key)
            # trainable mask is built later from the one shared eval_shape
            base_specs_fn = specs_fn
            specs_fn = lambda **kw: lora_param_specs(base_specs_fn(**kw), lora_cfg)

        pp = int(mesh.shape.get("pipe", 1))
        num_micro_in_step = sched["num_microbatches"]
        eval_loss_fn = loss_fn
        if pp > 1:
            # pipeline path: microbatching moves inside the pipelined loss
            # (reference base.py:374-383 run_train); layer stack sharded over
            # "pipe" IS the partitioning.  vp > 1 stores the stack in the
            # interleaved [vp, pp, Lc, ...] layout (reference VPP,
            # base.py:85,155) — note checkpoints then carry that layout.
            from jax.sharding import PartitionSpec as P

            from neuronx_distributed_training_tpu.parallel.pipeline import (
                MANUAL_VJP_SCHEDULES,
                pipeline_loss,
                pipeline_loss_and_grad,
                resolve_schedule,
                stage_layer_slice,
                to_interleaved,
            )
            from neuronx_distributed_training_tpu.trainer.step import microbatch_split

            vp = int(mesh_cfg.virtual_pipeline_model_parallel_size or 1)
            # fail early with a clear message instead of an opaque GSPMD error
            groups = family.moe_groups(model_cfg)
            if groups is None:
                stage_layer_slice(
                    int(getattr(model_cfg, "num_layers", 0) or 0), pp, vp)
            elif groups % (pp * vp) != 0:
                # pipe slices whole (MoE + dense) groups — with vp, every
                # chunk holds whole groups too (chunk layers = Gc*f, and
                # to_interleaved reshapes the [G]-leading moe/dense leaves
                # consistently with the flat [L] attn/norm leaves)
                raise ValueError(
                    f"num_layers {model_cfg.num_layers} / moe frequency "
                    f"{model_cfg.num_layers // groups} = {groups} groups, not "
                    f"divisible by pp*vp = {pp}*{vp}"
                )
            # the family's hooks (it refuses here what it cannot pipeline)
            (embed_fn, stage_fn, stage_loss_fn), hook_opts = family.pipeline(
                model_cfg, policy, shift_labels=shift_labels
            )
            nm = sched["num_microbatches"]
            if alignment in ("dpo", "orpo", "kto"):
                # preference losses pipeline via the concatenated forward
                # (reference base_dpo.py:68-88 runs chosen+rejected through
                # NxDPPModel as one doubled batch); every family pipelines —
                # the head_fn (final norm + lm head) is the only per-family bit
                from neuronx_distributed_training_tpu.alignment.dpo import (
                    preference_pipeline_hooks,
                )

                head_fn = family.head(model_cfg, policy)
                # reference parity: the HF models add the router aux loss
                # only when ``labels`` is passed; the DPO/ORPO path
                # computes logits without labels, so no aux term here
                # (stage_aux stays — MoE stages return (x, aux) tuples)
                hook_opts = dict(hook_opts, aux_inv_layers=0.0)
                if alignment == "kto":
                    # single-sequence batches: embed/stage pass through, only
                    # the loss hook changes (no chosen/rejected concat)
                    from neuronx_distributed_training_tpu.alignment.kto import (
                        kto_pipeline_hooks,
                    )

                    embed_fn, stage_fn, stage_loss_fn = kto_pipeline_hooks(
                        embed_fn, stage_fn, head_fn, beta=beta,
                        desirable_weight=float(
                            align_params.get("desirable_weight", 1.0)),
                        undesirable_weight=float(
                            align_params.get("undesirable_weight", 1.0)),
                    )
                else:
                    embed_fn, stage_fn, stage_loss_fn = preference_pipeline_hooks(
                        embed_fn, stage_fn, head_fn, mode=alignment, beta=beta
                    )
            stage_aux = bool(hook_opts.get("stage_aux"))
            aux_scale = float(hook_opts.get("aux_inv_layers", 0.0)) / nm
            needs_rng = bool(hook_opts.get("needs_rng"))

            # schedule selection: the memory-bounded manual-vjp 1F1B is the
            # production default whenever the model/loss combination supports
            # it (reference run_train's 1F1B engine, base.py:374-383 — O(pp)
            # in-flight activations instead of the autodiff wavefront's
            # O(nm + pp) per-tick residuals); `pipeline.schedule` in the
            # distributed_strategy block forces either schedule explicitly
            pipe_knobs = dict(
                (cfg.get("distributed_strategy", {}) or {}).get("pipeline", {})
                or {}
            )
            pp_schedule = resolve_schedule(
                pipe_knobs.get("schedule", "auto"),
                family.manual_vjp_refusal(model_cfg),
                {
                    "pipeline_model_parallel_size": pp,
                    "virtual_pipeline_model_parallel_size": vp,
                    "context_parallel_size": int(
                        mesh_cfg.context_parallel_size or 1),
                    "alignment": (alignment
                                  if alignment in ("dpo", "orpo", "kto")
                                  else None),
                    "lora": bool(lora_block),
                },
            )
            logger.info("pipeline schedule: %s (pp=%d, vp=%d)", pp_schedule, pp, vp)

            def loss_fn(p, batch, key):  # noqa: F811 — pipelined replacement
                mbs = microbatch_split(batch, nm)
                if needs_rng and key is not None:
                    mbs = dict(mbs)
                    mbs["_rng"] = jax.random.split(key, nm)
                loss = pipeline_loss(
                    p, p["layers"], mbs,
                    embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=stage_loss_fn,
                    mesh=mesh, num_microbatches=nm, virtual_pipeline_size=vp,
                    stage_aux=stage_aux, aux_scale=aux_scale,
                )
                return loss, {}

            # eval reuses the pipelined loss: under pp the layer stack lives in
            # the pipeline layout (interleaved when vp>1), so the plain forward
            # cannot run on it; val batches must be gbs-shaped to satisfy the
            # microbatch split — checked here instead of failing deep in
            # shard_map
            if val_data_module is not None:
                vgbs = getattr(val_data_module, "global_batch_size", None)
                if vgbs is not None and int(vgbs) != int(sched["global_batch_size"]):
                    raise ValueError(
                        f"under pipeline parallelism validation batches must "
                        f"match the train global_batch_size "
                        f"{sched['global_batch_size']} (val module has {vgbs}): "
                        f"the pipelined eval loss microbatches the same way"
                    )
            eval_loss_fn = loss_fn

            if pp_schedule in MANUAL_VJP_SCHEDULES:
                # train-step grads come from the manual-vjp tick loop (plain
                # 1F1B, the circular interleave when vp > 1, or the ZB-H1
                # dgrad/wgrad split); eval keeps the autodiff wavefront loss
                # above (it only needs the forward value)
                (head_hidden_fn, head_params_of, head_weight_of,
                 fold_head_grads) = family.onef1b_head(model_cfg, policy)

                def pp_loss_and_grad(p, batch, key):
                    mbs = microbatch_split(batch, nm)
                    if needs_rng and key is not None:
                        mbs = dict(mbs)
                        mbs["_rng"] = jax.random.split(key, nm)
                    loss, g = pipeline_loss_and_grad(
                        p, p["layers"], mbs,
                        embed_fn=embed_fn, stage_fn=stage_fn,
                        head_hidden_fn=head_hidden_fn,
                        head_params=head_params_of(p),
                        head_weight=head_weight_of(p),
                        mesh=mesh, num_microbatches=nm,
                        virtual_pipeline_size=vp,
                        zero_bubble=(pp_schedule == "1f1b-zb"),
                        stage_aux=stage_aux, aux_scale=aux_scale,
                        shift_labels=shift_labels,
                        double_buffer=overlap_cfg.pp_double_buffer,
                    )
                    # assemble the params-shaped grad tree: start from the
                    # embed-path cotangent (zeros off the embed path), add
                    # the layer-stack grads, fold the head grads back in
                    grads = dict(g["params_from_embed"])
                    grads["layers"] = jax.tree_util.tree_map(
                        lambda a, d: a + d.astype(a.dtype),
                        grads["layers"], g["layers"],
                    )
                    grads = fold_head_grads(
                        grads, g["head_params"], g["head_weight"]
                    )
                    return loss, {}, grads
            else:
                pp_loss_and_grad = None
            pspecs = specs_fn(pipeline=True)
            if vp > 1:
                flat_builder = param_builder

                def param_builder(key):
                    p = flat_builder(key)
                    return {**p, "layers": to_interleaved(p["layers"], pp, vp)}

                # [L, ...] -> [vp, pp, Lc, ...]: spec grows (vp, pipe, Lc) dims
                pspecs["layers"] = jax.tree_util.tree_map(
                    lambda s: P(None, s[0], None, *tuple(s)[1:]), pspecs["layers"],
                    is_leaf=lambda x: isinstance(x, P),
                )
            num_micro_in_step = 1
        else:
            pp_schedule = None
            pp_loss_and_grad = None
            pspecs = specs_fn()
        opt_block = dict((cfg.get("model", {}) or {}).get("optim", {}) or {})
        opt_cfg = AdamWConfig.from_config(opt_block, cfg.get("trainer", {}))
        zero1 = bool(cfg.get("distributed_strategy", {}).get("zero1", True))
        # weight EMA (reference exp_manager.ema -> NeMo EMA callback,
        # utils/exp_manager.py:298-305); lives inside the optimizer state
        ema_block = dict((cfg.get("exp_manager", {}) or {}).get("ema", {}) or {})
        ema_cfg = (
            EMAConfig.from_config(ema_block) if ema_block.get("enable") else None
        )
        # numerics flight recorder (telemetry.health) + tensor numerics
        # observatory (telemetry.tensorstats): parsed here — before the
        # optimizer state exists — because enabling either adds its subtree
        # to opt_state (and therefore to its specs and checkpoints);
        # ExpManager re-parses the same block for the host-side knobs
        from neuronx_distributed_training_tpu.telemetry import TelemetryConfig

        _tel_cfg = TelemetryConfig.from_config(
            (cfg.get("exp_manager", {}) or {}).get("telemetry")
        )
        health_cfg = _tel_cfg.health
        tensorstats_cfg = _tel_cfg.tensorstats
        abstract_params = jax.eval_shape(param_builder, init_key)
        if trainable is None and lora_block:
            # path-derived 0/1 scalars; reuses the one abstract trace
            from neuronx_distributed_training_tpu.peft import trainable_mask

            trainable = trainable_mask(abstract_params)
        # full ZeRO-1 including the embedding: the pipeline embed hooks use the
        # one-hot matmul form (ops.linear.apply_embedding via_matmul) so no
        # gather-transpose scatter reaches the partitioner under manual pipe
        ospecs = opt_state_specs(
            abstract_params, pspecs, mesh, zero1=zero1, policy=policy,
            ema=ema_cfg is not None, health=health_cfg.enabled,
        )
        bucket_plan = None
        if zero1 and overlap_cfg.zero1_bucket_mb > 0:
            from neuronx_distributed_training_tpu.telemetry.health import (
                grad_group_of,
            )

            bucket_plan = build_bucket_plan(
                abstract_params, pspecs, ospecs["mu"], mesh,
                bucket_mb=overlap_cfg.zero1_bucket_mb,
                group_fn=grad_group_of,
            )
            if bucket_plan is not None:
                logger.info("engineered overlap: %s", bucket_plan.describe())

        # tensorstats slots join the opt-state specs AFTER bucket planning:
        # the bucket phase records the packed payload of each combined
        # all-gather, so its state slots are named by the plan's buckets
        ts_bucket_groups: tuple = ()
        if tensorstats_cfg.enabled:
            from neuronx_distributed_training_tpu.telemetry.tensorstats import (
                tensorstats_state_specs,
            )

            if tensorstats_cfg.buckets and bucket_plan is not None:
                ts_bucket_groups = tuple(
                    b.name for b in bucket_plan.buckets if b.ag)
            ospecs["tensorstats"] = tensorstats_state_specs(
                tensorstats_cfg, abstract_params,
                bucket_groups=ts_bucket_groups)

        max_steps = int((cfg.get("trainer", {}) or {}).get("max_steps", 100))
        lr_schedule = build_lr_schedule(opt_block, max_steps_default=max_steps)
        exp_block = dict(cfg.get("exp_manager", {}) or {})
        step_fn = make_train_step(
            loss_fn, opt_cfg, lr_schedule, policy,
            num_microbatches=num_micro_in_step,
            # reference log_parameter_norm / log_gradient_norm
            # (base.py:397-452): per-step norms in the metrics dict -> loggers
            log_param_norm=bool(exp_block.get("log_parameter_norm", False)),
            log_gradient_norm=bool(exp_block.get("log_gradient_norm", False)),
            trainable_mask=trainable,
            ema_cfg=ema_cfg,
            param_specs=pspecs,
            loss_and_grad_fn=pp_loss_and_grad,
            health_cfg=health_cfg,
            bucket_plan=bucket_plan,
            prefetch_ag=overlap_cfg.prefetch_ag,
            tensorstats_cfg=tensorstats_cfg,
            after_update=family.after_update(model_cfg),
        )
        # params and optimizer state are donated, under EMA too: an earlier
        # runtime refused to donate an optimizer state carrying the EMA tree
        # (INVALID_ARGUMENT); on jaxlib 0.9.0 / libtpu 0.0.34 a tiny EMA run
        # with full donation trains clean on a v5e (PR 21's chip run)
        donate = True
        jstep = jit_train_step(step_fn, mesh, pspecs, ospecs, donate=donate)
        eval_fn = jax.jit(make_eval_step(eval_loss_fn)) if val_data_module else None

        return StepProgram(
            cfg=cfg, mesh=mesh, mesh_cfg=mesh_cfg, policy=policy, sched=sched,
            seed=seed, alignment=alignment, align_params=align_params,
            model_cfg=model_cfg, loss_fn=loss_fn, eval_loss_fn=eval_loss_fn,
            forward_logits=forward_logits, param_builder=param_builder,
            init_key=init_key, abstract_params=abstract_params,
            pspecs=pspecs, ospecs=ospecs, opt_cfg=opt_cfg, ema_cfg=ema_cfg,
            health_cfg=health_cfg, tensorstats_cfg=tensorstats_cfg,
            tensorstats_bucket_groups=ts_bucket_groups,
            trainable=trainable, lora_block=lora_block,
            jstep=jstep, eval_fn=eval_fn, data_module=data_module,
            val_data_module=val_data_module, shift_labels=shift_labels,
            pipeline_schedule=pp_schedule, num_micro_in_step=num_micro_in_step,
            max_steps=max_steps, donate=donate,
        )

    @classmethod
    def _materialize(
        cls, asm: StepProgram, *, devices: list, enable_checkpointing: bool
    ) -> "Trainer":
        """Turn a :class:`StepProgram` into a live session: sharded-at-birth
        param/opt-state init, warm start, sharding validation, exp manager,
        checkpointing, and the DPO/KTO reference-logprob pre-fit hook."""
        cfg, mesh, mesh_cfg = asm.cfg, asm.mesh, asm.mesh_cfg
        policy, sched, seed = asm.policy, asm.sched, asm.seed
        model_cfg, loss_fn = asm.model_cfg, asm.loss_fn
        pspecs, ospecs = asm.pspecs, asm.ospecs
        param_builder, init_key = asm.param_builder, asm.init_key
        ema_cfg, health_cfg = asm.ema_cfg, asm.health_cfg
        alignment, forward_logits = asm.alignment, asm.forward_logits
        data_module = asm.data_module
        val_data_module = asm.val_data_module
        jstep, eval_fn = asm.jstep, asm.eval_fn
        pp_schedule, max_steps = asm.pipeline_schedule, asm.max_steps
        pp = int(mesh.shape.get("pipe", 1))

        # materialize sharded-at-birth: jit with out_shardings creates every
        # leaf directly on its own devices — no full-model host/single-device
        # copy ever exists (cf. reference meta_device_init)
        import functools
        from jax.sharding import NamedSharding, PartitionSpec as P

        ns = functools.partial(NamedSharding, mesh)
        shardings = lambda specs: jax.tree_util.tree_map(
            ns, specs, is_leaf=lambda x: isinstance(x, P)
        )
        with startup_phase("startup/init_params"), mesh, shd.use_mesh(mesh):
            params = jax.jit(
                param_builder, out_shardings=shardings(pspecs)
            )(init_key)

        # warm start BEFORE the optimizer state is built: fp32 master weights
        # (and the EMA tree) must seed from the RESTORED params — the update
        # derives new params from opt_state["master"], so a master copied
        # from random init would silently void the warm start on step 1
        # (reference weight_init_only + resume_from_checkpoint,
        # nlp_overrides.py:541-568)
        warm_path = (cfg.get("exp_manager", {}) or {}).get("resume_from_checkpoint")
        if warm_path and bool((cfg.get("model", {}) or {}).get("weight_init_only")):
            warm_ck = Checkpointer(CheckpointConfig(dir=str(warm_path)))
            try:
                params = warm_ck.restore_params_only(
                    params, mesh=mesh, param_specs=pspecs
                )
            finally:
                warm_ck.close()
            logger.info("warm start: params restored from %s", warm_path)

        with startup_phase("startup/init_opt_state"), \
                mesh, shd.use_mesh(mesh):
            opt_state = jax.jit(
                functools.partial(
                    init_opt_state, policy=policy,
                    ema=ema_cfg is not None,
                    health=health_cfg.enabled,
                    tensorstats=asm.tensorstats_cfg,
                    tensorstats_bucket_groups=asm.tensorstats_bucket_groups),
                out_shardings=shardings(ospecs),
            )(params)

        # sharding sanity gate (SURVEY.md §5.2 "jit-time shape/sharding
        # assertions" — the TPU-native analogue of the reference's
        # HLO-consistency discipline): fail fast on silent replication or a
        # dropped constraint instead of discovering it as a perf mystery.
        # DEFAULT ON since round 3 — it is a pure metadata comparison (no
        # device work); set debug.validate_sharding: false to opt out.
        if bool((cfg.get("debug", {}) or {}).get("validate_sharding", True)):
            from neuronx_distributed_training_tpu.utils.debug import (
                assert_tree_sharding,
            )

            assert_tree_sharding(params, pspecs, mesh)
            assert_tree_sharding(opt_state, ospecs, mesh)
            logger.info("debug.validate_sharding: params + opt state verified")

        if data_module is None:
            # deferred ``data.synthetic: true`` (build_data_module had no vocab
            # hint before the model existed); any other source was built above
            seq = int((cfg.get("data", {}) or {}).get("seq_length", 2048))
            data_module = SyntheticDataModule(
                vocab_size=model_cfg.vocab_size,
                seq_len=seq,
                global_batch_size=sched["global_batch_size"],
                seed=seed,
            )

        # transient-read retry knobs (``data.io_retries`` /
        # ``data.io_retry_backoff_seconds``) imposed on whatever module the
        # build produced — attributes, not ctor args, so custom test doubles
        # keep working (without the attributes they simply don't retry)
        data_block = dict(cfg.get("data", {}) or {})
        for key, cast in (("io_retries", int),
                          ("io_retry_backoff_seconds", float)):
            if key in data_block and hasattr(data_module, key):
                setattr(data_module, key, cast(data_block[key]))

        with startup_phase("startup/exp_manager"):
            exp = ExpManager.from_config(
                cfg, global_batch_size=sched["global_batch_size"])

        # -- telemetry wiring: MFU reference + the static run facts the
        # compile census persists to run_summary.json.  The analytic FLOPs
        # estimate (utils.perf, the reference's llama_perf_estimate role) is
        # per-family; throughput itself stays the one source of truth —
        # mfu derives from its tokens_per_sec at each logging boundary.
        # (Down to the checkpointer: ``startup/telemetry_arming``.)
        t_arming = time.perf_counter()
        from neuronx_distributed_training_tpu.utils import perf as _perf

        seq_len = int((cfg.get("data", {}) or {}).get("seq_length", 0) or 0) \
            or int(getattr(data_module, "seq_len", 0) or 0)
        if exp.throughput.seq_len == 0:
            exp.throughput.seq_len = seq_len
        n_chips = int(mesh.devices.size)
        from neuronx_distributed_training_tpu.parallel.pipeline import (
            MANUAL_VJP_SCHEDULES,
            predicted_bubble_fraction,
            work_table,
        )

        run_facts: dict = {
            "model_family": type(model_cfg).__name__,
            "n_chips": n_chips,
            "seq_len": seq_len,
            "global_batch_size": int(sched["global_batch_size"]),
            "pipeline_schedule": pp_schedule,
            "bubble_fraction_predicted": round(predicted_bubble_fraction(
                pp_schedule, pp, int(sched["num_microbatches"]),
                int(mesh_cfg.virtual_pipeline_model_parallel_size or 1)), 6),
        }
        moe_cfg = getattr(model_cfg, "moe", None)
        if moe_cfg is not None and moe_cfg.dropless:
            # null = not known (the census's trace did not run); the census
            # fills in how the block was partitioned.  Absent = no such block
            run_facts["moe_token_shards"] = None
        run_facts.update(model_cfg.family.run_facts(model_cfg, sched))
        # the manual-vjp schedules run the WORK-COMPACTED executor: record
        # its per-step tick counts (compacted span + per-kind active ticks
        # vs the old lockstep trip count) so the measured timelines are
        # interpretable from run_summary.json alone
        ticks_per_step = None
        if pp_schedule in MANUAL_VJP_SCHEDULES:
            ticks_per_step = work_table(
                pp_schedule, pp, int(sched["num_microbatches"]),
                int(mesh_cfg.virtual_pipeline_model_parallel_size or 1),
            ).tick_counts()
            run_facts["pipeline_ticks_per_step"] = ticks_per_step
        # arm the trace capture's pipeline-timeline reconstruction: with
        # pp > 1 a closed telemetry.trace window reconstructs the per-stage
        # tick Gantt and writes bubble_fraction_measured beside the
        # predicted run fact (telemetry.step_timeline)
        from neuronx_distributed_training_tpu.telemetry.step_timeline import (
            pipeline_facts,
        )

        exp.set_pipeline_facts(pipeline_facts(
            pp_schedule, pp, int(sched["num_microbatches"]),
            int(mesh_cfg.virtual_pipeline_model_parallel_size or 1),
            run_facts["bubble_fraction_predicted"],
            ticks_per_step=ticks_per_step))
        # the chip's peak and ICI table, keyed by device_kind.  Outside the
        # observability try-blocks below: an unknown TPU raises here instead
        # of being priced with another chip's numbers
        from neuronx_distributed_training_tpu.autotune.topology import (
            resolve_topology,
        )

        peak_tflops = _perf.detect_peak_tflops(devices[0])
        topo = resolve_topology(device=devices[0])
        # arm the interconnect join (telemetry.comms): the cost model's
        # per-axis byte volumes + the topology's ICI prior let a closed
        # trace window turn per-class wire seconds into achieved_gbps /
        # efficiency — the "comms" section of trace_summary/run_summary
        try:
            from neuronx_distributed_training_tpu.autotune.cost_model import (
                ModelFacts,
                collective_byte_volumes,
            )
            from neuronx_distributed_training_tpu.telemetry.comms import (
                MESH_TO_AXIS,
            )

            plan_facts = ModelFacts.from_config(cfg)
            declared = plan_facts.declared_plan_for(n_chips)
            if declared is not None:
                exp.set_comms_facts({
                    "byte_volumes": collective_byte_volumes(
                        plan_facts, declared),
                    "axis_sizes": {MESH_TO_AXIS[k]: int(v)
                                   for k, v in dict(mesh.shape).items()
                                   if k in MESH_TO_AXIS},
                    "peak_bandwidth_bytes": topo.ici_bandwidth_bytes,
                    "topology": topo.name,
                })
        except Exception as e:  # noqa: BLE001 — observability, not load-bearing
            logger.warning("comms telemetry arming unavailable: %s", e)
        try:
            fwd_flops = flops_for_model(model_cfg, seq_len)
            run_facts["fwd_flops_per_token"] = fwd_flops
            run_facts["peak_tflops_per_chip"] = peak_tflops
            if exp.telemetry.mfu:
                exp.set_mfu_reference(
                    train_step_flops_per_token=(
                        _perf.train_step_flops_per_token(fwd_flops)),
                    n_chips=n_chips,
                    # no peak off the TPU: tokens/s/chip is logged, MFU is not
                    peak_tflops_per_chip=peak_tflops or 0.0,
                )
        except Exception as e:  # noqa: BLE001 — MFU is observability, not load-bearing
            logger.warning("MFU estimation unavailable for %s: %s",
                           type(model_cfg).__name__, e)

        startup_add("startup/telemetry_arming", t_arming)

        checkpointer = None
        if enable_checkpointing:
            with startup_phase("startup/checkpointer"):
                ck_cfg = CheckpointConfig.from_config(cfg)
                ck_cfg = dataclasses.replace(ck_cfg, dir=exp.checkpoint_dir)
                checkpointer = Checkpointer(ck_cfg)

        from neuronx_distributed_training_tpu.trainer.elastic import (
            ElasticConfig,
        )

        elastic = ElasticConfig.from_config(
            (cfg.get("exp_manager", {}) or {}).get("elastic"))

        pre_fit = None
        if alignment in ("dpo", "kto"):
            if alignment == "dpo":
                from neuronx_distributed_training_tpu.alignment.dpo import (
                    iter_reference_logprobs as _ref_iter,
                )

                _marker, _sidecar_name = (
                    "reference_chosen_logps", "dpo_reference_logps.npz")
            else:
                from neuronx_distributed_training_tpu.alignment.kto import (
                    iter_reference_logprobs_kto as _ref_iter,
                )

                _marker, _sidecar_name = (
                    "reference_logps", "kto_reference_logps.npz")

            def _attach_reference_columns(dm, ref_params, sidecar, tag):
                """Streamed frozen-policy pass over ONE data module: per-batch
                compute (single shared jit), progress logging, and periodic
                sidecar spill with a ``_done_upto`` cursor so a preempted
                100k-pair pass resumes where it stopped instead of restarting
                (VERDICT r2 item 10)."""
                import os

                if not hasattr(dm, "attach_reference_logprobs"):
                    return  # caller supplied reference columns already
                if _marker in getattr(dm, "arrays", {}):
                    return
                n = dm.sampler.total_samples
                bs = min(dm.global_batch_size, n)
                done = 0
                cols: dict[str, np.ndarray] = {}
                # column set the pass will produce for THIS data module —
                # a sidecar from a different config (e.g. written under
                # kto kl_estimator=batch_mean, resumed under mismatched)
                # must trigger recompute, not a KeyError in the jitted step
                expected = {_marker}
                if _marker == "reference_chosen_logps":
                    expected.add("reference_rejected_logps")
                if _marker == "reference_logps" and "kl_input_ids" in getattr(
                        dm, "arrays", {}):
                    expected.add("reference_kl_logps")
                loaded = _sidecar_load(sidecar, tag)
                if loaded is not None:
                    done, cols = loaded
                    if set(cols) != expected:
                        logger.warning(
                            "%s sidecar %s has columns %s but this config "
                            "needs %s; recomputing", tag, sidecar,
                            sorted(cols), sorted(expected),
                        )
                        done, cols = 0, {}
                    elif any(len(v) != n for v in cols.values()):
                        # dataset grew/shrank since the sidecar was written:
                        # stale columns would crash (or silently mis-attach)
                        logger.warning(
                            "%s sidecar %s has %d-sample columns but the "
                            "dataset has %d; recomputing", tag, sidecar,
                            len(next(iter(cols.values()))), n,
                        )
                        done, cols = 0, {}
                    elif done >= n:
                        dm.attach_reference_logprobs(cols)
                        logger.info("%s reference logps restored from %s", tag, sidecar)
                        return
                    else:
                        logger.info(
                            "%s reference pass resuming at %d/%d from %s",
                            tag, done, n, sidecar,
                        )
                # batches restart AT the cursor (not at cursor rounded to a
                # bs multiple): a resume with a different global_batch_size
                # must still recompute every remaining sample
                import time as _time

                from neuronx_distributed_training_tpu.data.loader import (
                    PrefetchIterator,
                )

                starts = list(range(done, n, bs))
                total = len(starts)
                log_every = max(1, total // 20)
                spill_every = max(1, total // 10)
                # same host/device overlap as the fit loop: row slicing
                # happens on the prefetch thread, not between dispatches
                batches = PrefetchIterator(
                    ({k: v[i:min(i + bs, n)] for k, v in dm.arrays.items()}
                     for i in starts)
                )
                start_done, t0 = done, _time.perf_counter()
                try:
                    for j, part in enumerate(_ref_iter(ref_params, batches,
                                                       forward_logits)):
                        if not cols:
                            cols = {k: np.empty((n,), v.dtype)
                                    for k, v in part.items()}
                        i = starts[j]
                        for k, v in part.items():
                            cols[k][i:i + len(v)] = v
                        done = min(i + bs, n)
                        if (j + 1) % log_every == 0 or done >= n:
                            rate = (done - start_done) / max(
                                _time.perf_counter() - t0, 1e-9)
                            logger.info(
                                "%s reference-logp pass: %d/%d samples "
                                "(%.0f samples/s, ETA %.0fs)",
                                tag, done, n, rate, (n - done) / max(rate, 1e-9),
                            )
                        if sidecar is not None and ((j + 1) % spill_every == 0
                                                    or done >= n):
                            _sidecar_store(sidecar, done, cols)
                finally:
                    batches.close()
                dm.attach_reference_logprobs(cols)

            def pre_fit(trainer: "Trainer") -> None:
                """Frozen-policy reference-logprob pass + column attach
                (reference base_dpo.py:23-66 on_train_start; same protocol
                for the KTO extension).

                Runs BEFORE checkpoint resume (fit() ordering): the reference
                logps must come from the frozen INITIAL policy, and at that
                point ``trainer.params`` still hold the deterministic initial
                (or warm-start) weights the original run started from.  The
                columns are cached to a sidecar so resumes skip the pass.
                Both the train AND val modules get columns — a val batch
                without them would KeyError inside the jitted eval step
                (ADVICE r2)."""
                import os

                ref_params = trainer.params
                # interleaving only happens when the pipeline branch ran
                # (pp > 1 AND vp > 1); gate on both or a flat stack would be
                # "de-interleaved" into garbage shapes
                vp_now = int(mesh_cfg.virtual_pipeline_model_parallel_size or 1)
                if pp > 1 and vp_now > 1:
                    # interleaved layout -> flat [L] for the plain forward
                    # (a reshape; the reference pass is compute-once)
                    from neuronx_distributed_training_tpu.parallel.pipeline import (
                        from_interleaved,
                    )

                    ref_params = dict(trainer.params)
                    ref_params["layers"] = from_interleaved(
                        trainer.params["layers"])
                ck_dir = (str(trainer.checkpointer.config.dir)
                          if trainer.checkpointer is not None else None)

                def _sidecar(suffix):
                    if ck_dir is None:
                        return None
                    stem, ext = os.path.splitext(_sidecar_name)
                    return os.path.join(ck_dir, stem + suffix + ext)

                _attach_reference_columns(
                    trainer.data_module, ref_params, _sidecar(""), "train")
                if trainer.val_data_module is not None:
                    _attach_reference_columns(
                        trainer.val_data_module, ref_params, _sidecar("_val"),
                        "val")

        return cls(
            cfg=cfg, mesh=mesh, policy=policy, model_cfg=model_cfg, loss_fn=loss_fn,
            params=params, opt_state=opt_state, param_specs=pspecs, opt_specs=ospecs,
            train_step=jstep, eval_step=eval_fn, data_module=data_module,
            val_data_module=val_data_module, exp=exp, checkpointer=checkpointer,
            max_steps=max_steps, pre_fit=pre_fit, ema_cfg=ema_cfg,
            pipeline_schedule=pp_schedule, run_facts=run_facts,
            donate=asm.donate, elastic=elastic,
        )

    # -- resume -------------------------------------------------------------

    @property
    def consumed_samples(self) -> int:
        """Derived from TRAINED steps (the reference's
        ``compute_consumed_samples``, ``data/base.py:33-47``) — NOT from the
        sampler's yield counter, which runs ahead of training by the prefetch
        queue depth."""
        return self.step * int(self.data_module.global_batch_size)

    def maybe_resume(self) -> bool:
        """Restore newest checkpoint if one exists (reference ``resume_if_exists``)."""
        if self.checkpointer is None or self.checkpointer.latest_step() is None:
            return False
        try:
            state = self.checkpointer.restore(
                self.params, self.opt_state,
                mesh=self.mesh, param_specs=self.param_specs,
                opt_specs=self.opt_specs,
            )
        except Exception as orig:
            # enabling telemetry.health or telemetry.tensorstats adds a
            # subtree to the opt state, so a checkpoint written BEFORE the
            # knob was turned on mismatches the template: retry without the
            # newer subtree(s) and keep the freshly initialized (already
            # correctly sharded) counters — an operator flipping a telemetry
            # knob on must not lose their run.  Candidates are tried
            # narrowest-first (newest feature alone, then each alone, then
            # both) so a checkpoint that DOES carry one subtree keeps it.  A
            # retry chain that fails too re-raises the ORIGINAL error (the
            # real root cause), not a retry's.
            telemetry_subtrees = [k for k in ("tensorstats", "health")
                                  if k in self.opt_state]
            if not telemetry_subtrees:
                raise
            candidates = [(k,) for k in telemetry_subtrees]
            if len(telemetry_subtrees) > 1:
                candidates.append(tuple(telemetry_subtrees))
            state = None
            stripped_of: tuple = ()
            for drop in candidates:
                logger.warning(
                    "resume: full restore failed (%s: %s); retrying without "
                    "the telemetry %s subtree(s) in case the checkpoint "
                    "predates them",
                    type(orig).__name__, orig, "/".join(drop),
                )
                stripped = {k: v for k, v in self.opt_state.items()
                            if k not in drop}
                stripped_specs = {k: v for k, v in self.opt_specs.items()
                                  if k not in drop}
                try:
                    state = self.checkpointer.restore(
                        self.params, stripped,
                        mesh=self.mesh, param_specs=self.param_specs,
                        opt_specs=stripped_specs,
                    )
                    stripped_of = drop
                    break
                except Exception:
                    continue
            if state is None:
                raise orig
            restored_opt = dict(state.opt_state)
            if "health" in stripped_of:
                # fresh counters, but steps_seen MUST align with the restored
                # trainer step: last_nonfinite_step derives from it, and a
                # misaligned value would name the wrong step (and RNG recipe)
                # in every future anomaly bundle
                health = dict(self.opt_state["health"])
                health["steps_seen"] = jnp.asarray(int(state.step), jnp.int32)
                restored_opt["health"] = health
            if "tensorstats" in stripped_of:
                # the cumulative observatory record simply starts fresh — the
                # stats are a streaming aggregate, not training state
                restored_opt["tensorstats"] = self.opt_state["tensorstats"]
            state.opt_state = restored_opt
            logger.info(
                "resume: checkpoint predates telemetry %s — restored without "
                "the subtree(s), counters start fresh at step %d",
                "/".join(stripped_of), int(state.step),
            )
        if self.fault_injector is not None:
            # drill injection point "restore": the checkpoint has been read
            # but nothing applied yet — a kill here must leave the save
            # intact and the next resume able to start over; sigterm mode is
            # a preemption notice landing mid-restore
            if self.fault_injector.maybe_fire("restore", int(state.step)):
                self.preemption_notice = (
                    "injected preemption notice (mid-restore)")
        self.params = state.params
        self.opt_state = state.opt_state
        self.step = state.step
        self.data_module.sampler.consumed_samples = state.consumed_samples
        logger.info(
            "resumed from step %d (consumed_samples=%d)", state.step, state.consumed_samples
        )
        return True

    # -- the loop -----------------------------------------------------------

    def fit(self) -> dict[str, float]:
        t_fit = time.perf_counter()
        import contextlib
        import signal
        import time as _time

        from neuronx_distributed_training_tpu.telemetry import (
            HangWatchdog,
            HealthMonitor,
            RecompileDetector,
            SpanTimer,
        )
        from neuronx_distributed_training_tpu.telemetry.tensorstats import (
            HIST_PREFIX as _TS_HIST_PREFIX,
        )

        tel = self.exp.telemetry
        # spans power both the per-boundary decomposition AND goodput; the
        # timer is pure perf_counter bookkeeping, so either knob arms it
        # The first fit() of a process continues the clock of the process's
        # start-up timeline (telemetry/spans.py::STARTUP): goodput's wall
        # then starts at process start, and the loop's spans up to the end
        # of the first boundary join the timeline's phases.  A later fit()
        # in the same process (tests, drills) starts its own clock.
        startup = claim_startup()
        if startup is not None and not (tel.spans or tel.goodput):
            startup.close()
            startup = None
        spans = SpanTimer(enabled=tel.spans or tel.goodput,
                          earlier=startup.timer if startup else None)
        startup_section: Optional[dict] = None
        detector = RecompileDetector()
        # numerics flight recorder: ring-buffers per-step forensic context
        # (host references only — no device fetch on healthy steps) and
        # applies the anomaly policy at the loop's existing sync boundaries
        hc = tel.health
        monitor = (
            HealthMonitor(
                hc, dump_dir=self.exp.log_dir, run_facts=self.run_facts,
                write_run_summary=self.exp.write_run_summary,
                rng_seed=STEP_KEY_SEED,
            )
            if hc.enabled else None
        )
        # (the hang watchdog is built AFTER the fleet/alert/control blocks
        # below: a bundle-only monitor armed there must reach it, and the
        # control plane decides whether a fire escapes the process)
        # -- fleet observability plane + declarative alerts (telemetry.fleet
        # / telemetry.alerts — docs/observability.md "Fleet observability"):
        # this host appends a beacon to fleet/host_<id>.jsonl at every
        # logging boundary; rank 0 folds every host's stream into
        # fleet_summary.json (straggler attribution, quiet-host findings);
        # the alert rules evaluate over the streamed boundary metrics.
        # Everything is host-side bookkeeping on already-fetched values —
        # zero new host syncs between boundaries, no graph changes.
        fleet = None
        if tel.fleet.enabled:
            try:
                from neuronx_distributed_training_tpu.telemetry import (
                    FleetPlane,
                )

                host = int(jax.process_index())
                fleet = FleetPlane(
                    tel.fleet, self.exp.log_dir, host=host,
                    aggregate=(host == 0),
                    write_run_summary=self.exp.write_run_summary,
                )
            except Exception as e:  # noqa: BLE001 — observability must not
                logger.warning("fleet plane unavailable: %s", e)
        alerts = None
        if tel.alerts:
            from neuronx_distributed_training_tpu.telemetry import AlertEngine

            alerts = AlertEngine(
                tel.alerts, write_run_summary=self.exp.write_run_summary)
        # -- memory observability (telemetry.memory — docs/observability.md
        # "Memory observability"): per-device allocator stats across the
        # local mesh at every boundary (memory/ metrics through all sinks +
        # fleet beacons), ONE windowed device_memory_profile() capture
        # attributed to subsystems -> memory_summary.json, and OOM
        # forensics (a RESOURCE_EXHAUSTED escaping the step boundary dumps
        # oom_<step>/ with predicted-vs-actual in one artifact).  Host-side
        # only: zero graph changes, zero extra syncs between boundaries.
        memplane = None
        if tel.memory.enabled:
            try:
                from neuronx_distributed_training_tpu.autotune.cost_model import (  # noqa: E501
                    predicted_breakdown_for_config,
                )
                from neuronx_distributed_training_tpu.telemetry import (
                    MemoryPlane,
                )
                from neuronx_distributed_training_tpu.telemetry.memory import (  # noqa: E501
                    tree_bytes_by_subsystem,
                )

                memplane = MemoryPlane(
                    tel.memory, self.exp.log_dir,
                    devices=lambda: _local_mesh_devices(self.mesh),
                    tree_bytes_fn=lambda: tree_bytes_by_subsystem(
                        self.params, self.opt_state),
                    predicted=predicted_breakdown_for_config(
                        self.cfg, int(self.mesh.devices.size)),
                    run_facts=self.run_facts,
                    write_run_summary=self.exp.write_run_summary,
                )
            except Exception as e:  # noqa: BLE001 — observability must not
                logger.warning("memory plane unavailable: %s", e)
        # -- coordinated fleet control (trainer.control — docs/observability
        # .md "Fleet control"): every stop/checkpoint decision folds through
        # ONE tiny replicated collective at the deterministic boundary
        # cadence, so all hosts derive the SAME decision at the same step.
        # An alert halt, a health halt, a SIGTERM notice, or an operator
        # command on ONE host stops the whole fleet with a drained
        # emergency save instead of stalling the survivors at the next
        # collective rendezvous.
        ccfg = tel.control
        control = None
        if ccfg.enabled:
            try:
                from neuronx_distributed_training_tpu.trainer.control import (
                    ControlPlane,
                )

                chost = int(jax.process_index())
                control = ControlPlane(
                    ccfg, self.exp.log_dir, host=chost,
                    poll_commands=ccfg.poll_commands and chost == 0,
                    write_run_summary=self.exp.write_run_summary,
                    peer_words=self.control_peer_words,
                )
            except Exception as e:  # noqa: BLE001 — never kill the launch
                logger.warning("fleet control plane unavailable: %s", e)
        elif jax.process_count() > 1 and any(
                r.action == "halt" for r in tel.alerts):
            # without the control plane a halt decision is host-local: on a
            # metric that is not bit-identical across hosts, one host can
            # stop alone and stall the fleet at the next collective — the
            # consensus control word is the fix
            logger.warning(
                "multi-host run with action=halt alert rules and "
                "exp_manager.telemetry.control disabled: halt decisions "
                "are host-local; enable the control plane so stops are "
                "fleet-consistent (docs/observability.md 'Fleet control')")
        if monitor is None and (
                fleet is not None
                or control is not None
                or any(r.action == "dump" for r in tel.alerts)):
            # alert `action: dump` and the fleet's quiet-host findings both
            # reuse the flight recorder's bundle machinery; without the
            # health knob on, arm a bundle-only monitor (ring + forensic
            # writes — no in-graph probes, and with no health counters in
            # the metrics its boundary check is a no-op)
            monitor = HealthMonitor(
                hc, dump_dir=self.exp.log_dir, run_facts=self.run_facts,
                write_run_summary=self.exp.write_run_summary,
                rng_seed=STEP_KEY_SEED,
            )
        watchdog = (
            HangWatchdog(hc.watchdog_timeout_seconds, monitor,
                         abort=hc.watchdog_abort)
            if monitor is not None and hc.watchdog_timeout_seconds > 0
            else None
        )
        if watchdog is not None and control is not None and ccfg.hang_escape:
            # collective-hang escape (docs/observability.md "Fleet
            # control"): a boundary sync that exceeds the watchdog timeout
            # means a peer died mid-collective — after the hang_<step>/
            # bundle the survivor writes its final DYING beacon and the
            # control-trail exit note, then exits with the tagged
            # EXIT_HANG_ESCAPE code.  Survivors never hang forever; the
            # orchestrator restarts the incarnation and elastic resume +
            # integrity walk-back do the recovery.
            from neuronx_distributed_training_tpu.trainer.control import (
                EXIT_HANG_ESCAPE,
            )

            def _escape_note(what, step):
                control.note_exit(
                    "hang_escape",
                    f"boundary sync {what!r} exceeded "
                    f"{hc.watchdog_timeout_seconds:.0f}s at step {step}; "
                    f"exiting EXIT_HANG_ESCAPE")

            def _escape_beacon(what, step):
                if fleet is not None:
                    fleet.close(RuntimeError(
                        f"hang escape: {what} exceeded "
                        f"{hc.watchdog_timeout_seconds:.0f}s"), step=step)

            watchdog.arm_escape(EXIT_HANG_ESCAPE, _escape_note,
                                _escape_beacon)
        halted = False

        def _sync_guard(what):
            # arm the hung-device-sync watchdog around a blocking fetch
            return (watchdog.guard(what, self.step) if watchdog is not None
                    else contextlib.nullcontext())

        cfg_t = dict(self.cfg.get("trainer", {}) or {})
        val_interval = int(cfg_t.get("val_check_interval", 0) or 0)
        limit_val = int(cfg_t.get("limit_val_batches", 10) or 10)
        ck_every = (
            self.checkpointer.config.every_n_train_steps if self.checkpointer else 0
        )
        max_time = parse_max_time(cfg_t.get("max_time"))
        t_start = _time.monotonic()

        # preemption hook: SIGTERM (SLURM preemption / spot reclaim) requests a
        # graceful stop — checkpoint at the next step boundary, then exit clean
        # so resume_if_exists continues the run (reference: Lightning's
        # preemption plugin + SLURM requeue, train_setup.sh:28-29).  The
        # elastic grace window starts at the NOTICE, not at the boundary: the
        # emergency save's retry loop must give up before the fleet kills the
        # process (docs/elasticity.md "Grace window").
        from neuronx_distributed_training_tpu.trainer.elastic import (
            ElasticConfig,
        )

        el = self.elastic if self.elastic is not None else ElasticConfig()
        stop_requested: dict[str, Any] = {"reason": None, "deadline": None}

        def _request_stop(reason: str, condition: Optional[str] = None) -> None:
            # the grace deadline starts at the NOTICE (docs/elasticity.md);
            # `condition` additionally registers the control-word bit so the
            # next boundary fold shares the stop with the whole fleet —
            # without it (control disabled), the stop stays host-local
            stop_requested["reason"] = reason
            if stop_requested["deadline"] is None and el.grace_period_seconds > 0:
                stop_requested["deadline"] = (
                    _time.monotonic() + el.grace_period_seconds)
            if control is not None and condition is not None:
                control.request(condition, reason)

        def _on_sigterm(signum, frame):
            _request_stop("SIGTERM (preemption)", condition="preemption")

        old_handler = None
        try:
            old_handler = signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            pass  # not in the main thread (tests); preemption hook disabled

        resumed = False
        last_metrics: dict[str, float] = {}
        batches = None
        # the exception actually propagating out of THIS fit() — captured
        # explicitly because sys.exc_info() inside the finally would also
        # see an exception the CALLER is currently handling (fit() invoked
        # from an except block), mislabeling a clean run as a dying host in
        # the final fleet beacon
        fit_exc: Optional[BaseException] = None
        try:
            # real backend compiles with the step they hit -> compile_events
            # (inside the teardown scope, which detaches the listener again:
            # the process-wide listener must not outlive this fit())
            detector.watch_compiles(lambda: self.step)
            # the restart phase runs INSIDE the teardown scope: a restore
            # failure (corrupt checkpoint, drill restore-kill) must still
            # restore the SIGTERM handler, write the teardown summaries, and
            # close the exp manager — otherwise every faulted incarnation
            # leaks its log FileHandler and leaves a dead trainer's stop
            # closure bound to SIGTERM
            # restart-time replan (trainer.elastic.maybe_replan ran in the
            # CLI / drill harness BEFORE this trainer existed): account its
            # wall time as the "replan" span so goodput sees the full
            # restart cost
            if self.replan_record and not (
                    startup and "replan" in startup.timer.snapshot()):
                # (a replan the CLI bracketed is on the timeline already)
                spans.add_preexisting(
                    "replan",
                    float(self.replan_record.get("replan_seconds", 0.0) or 0.0))
            # pre_fit BEFORE resume: the DPO reference pass must see the
            # frozen initial policy, not resumed weights (see pre_fit
            # docstring).  Both are "restart" time for goodput: work a run
            # repeats after preemption that trains nothing.
            with spans.span("restart"):
                if self.pre_fit is not None:
                    self.pre_fit(self)
                resumed = self.maybe_resume()
                if resumed and monitor is not None and "health" in self.opt_state:
                    # align the boundary comparator with the RESTORED
                    # cumulative counter — otherwise the first boundary
                    # re-triggers the policy for an anomaly the previous
                    # incarnation handled (a permanent halt/restart loop
                    # under policy=halt)
                    monitor.seed_counters(
                        int(self.opt_state["health"]["nonfinite_count"]))
            # data-pipeline stats (telemetry.batch_stats): the accumulator
            # rides the prefetch thread — global_batches feeds it from the
            # host numpy batch before sharding, the boundary drains it into
            # the metric stream.  Attached before the iterator exists so the
            # first batch is already counted.
            batch_stats = None
            if tel.batch_stats and hasattr(self.data_module, "global_batches"):
                from neuronx_distributed_training_tpu.data.loader import (
                    BatchStats,
                )

                batch_stats = BatchStats(
                    pad_id=getattr(self.data_module, "pad_id", None))
                try:
                    self.data_module.batch_stats = batch_stats
                except AttributeError:  # a slotted test double: no hook
                    batch_stats = None
            # background prefetch: slow fetch_rows (arrow page-in, mmap
            # faults) must not stall dispatch (the reference's MpDeviceLoader
            # role); shard_batch uses an explicit NamedSharding, so it is
            # thread-safe.  AFTER resume: the sampler's consumed_samples
            # must be restored before the first fetch.
            batches = PrefetchIterator(
                self.data_module.sharded_batches(self.mesh),
                timeout_seconds=hc.data_wait_timeout_seconds,
                activity_fn=getattr(self.data_module, "last_io_activity",
                                    None))
            log_every = max(1, int(self.exp.log_every_n_steps))
            census_pending = tel.compile_census
            with self.mesh, shd.use_mesh(self.mesh):
                self.exp.step_timed()  # arm the step timer
                # restart time predates the window just armed: drop it from
                # the throughput exclusion (goodput still counts it)
                spans.take_excluded()
                first_dispatch = True
                last_fetch = self.step
                if startup is not None:
                    # entry of fit() to here; the restart inside it is a
                    # phase of its own and holds its part of the stretch
                    startup.timer.add(
                        "startup/fit_prologue",
                        time.perf_counter() - t_fit, begin=t_fit)
                while self.step < self.max_steps:
                    # device-time capture window (telemetry.trace): start/
                    # stop rides the same per-step cadence; steps outside
                    # the window are untouched (no syncs, no graph changes)
                    self.exp.maybe_trace(self.step)
                    if self.preemption_notice is not None:
                        # a sigterm-mode injection fired at the save/restore
                        # point (outside this loop's scope): honor it like a
                        # SIGTERM that landed there
                        _request_stop(self.preemption_notice,
                                      condition="preemption")
                        self.preemption_notice = None
                    if self.fault_injector is not None and \
                            self.fault_injector.maybe_fire("step", self.step):
                        # sigterm-mode injection: a preemption NOTICE — the
                        # step still runs, then the boundary takes the
                        # grace-window emergency checkpoint (kill mode raised
                        # out of maybe_fire instead)
                        _request_stop("injected preemption notice",
                                      condition="preemption")
                    with spans.span("data_wait"):
                        try:
                            batch = next(batches)
                        except DataStallError as stall:
                            # data-stall watchdog (telemetry.health.
                            # data_wait_timeout_seconds): feed the existing
                            # hang-watchdog bundle path — thread stacks + a
                            # device-safe forensic bundle — then let the
                            # curated error propagate instead of freezing.
                            # The transient-I/O retries already ran (and
                            # deferred this verdict) on the prefetch thread.
                            self.stop_class = "data_stall"
                            if control is not None:
                                control.note_exit("data_stall", str(stall))
                            if monitor is not None:
                                from neuronx_distributed_training_tpu.telemetry.flight_recorder import (  # noqa: E501
                                    _all_thread_stacks,
                                )

                                monitor.dump_hang(
                                    self.step, "data_wait",
                                    _all_thread_stacks())
                            raise
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(STEP_KEY_SEED), self.step)
                    if census_pending:
                        census_pending = False
                        self._compile_census(batch, key, spans)
                    # host-side metadata check only (shapes/dtypes — never
                    # values): a mid-run signature change means a retrace
                    detector.check("train_step", batch)
                    # the step annotation also bounds the trace capture's
                    # per-step device-time attribution, so it stays on for
                    # an open trace window even when spans are off
                    annot = (
                        jax.profiler.StepTraceAnnotation(
                            "train", step_num=self.step)
                        if tel.spans or self.exp.trace_active
                        else contextlib.nullcontext()
                    )
                    # "dispatch" is host enqueue time: under dispatch-ahead
                    # the device runs behind and this span stays tiny; device
                    # time that outran the host surfaces in host_sync instead.
                    # The first call of a still-jitted step (census off or
                    # failed) traces+compiles inline — count that one as
                    # "compile" so it stays out of the throughput window and
                    # goodput either way.
                    dispatch_span = "dispatch"
                    if first_dispatch:
                        first_dispatch = False
                        if hasattr(self.train_step, "lower"):
                            dispatch_span = "compile"
                    with spans.span(dispatch_span), annot:
                        self.params, self.opt_state, metrics = self.train_step(
                            self.params, self.opt_state, batch, key
                        )
                    if monitor is not None:
                        # host references only (device arrays stay unfetched);
                        # the batch fingerprint is the retrace detector's —
                        # one abstract-signature source of truth
                        monitor.record(
                            self.step, metrics,
                            fingerprint=detector.signature("train_step"),
                            spans=spans.snapshot() if spans.enabled else None,
                        )
                    self.step += 1
                    if max_time is not None and stop_requested["reason"] is None:
                        if _time.monotonic() - t_start > max_time:
                            if control is not None:
                                # host clocks disagree at the margin: fold
                                # the budget stop through the control word
                                # so the fleet stops at the same step
                                control.request(
                                    "max_time",
                                    f"max_time {cfg_t.get('max_time')}")
                            else:
                                stop_requested["reason"] = (
                                    f"max_time {cfg_t.get('max_time')}")
                    # host sync ONLY at logging/validation/checkpoint
                    # boundaries: between them the loop keeps dispatching
                    # ahead of the device (the reference batches metric
                    # fetches the same way via xm.add_step_closure,
                    # base.py:235-250).  Under the control plane a stop
                    # NOTICE never makes its own boundary: the decision must
                    # land at a step every host computes identically, or the
                    # fold collective itself would rendezvous-mismatch — the
                    # notice waits for the next deterministic boundary (and
                    # on a real fleet the host keeps dispatching steps until
                    # then, staying inside every collective).
                    boundary = (
                        self.step % log_every == 0
                        or self.step == self.max_steps
                        or (control is None
                            and stop_requested["reason"] is not None)
                        or (val_interval and self.step % val_interval == 0)
                        or (ck_every and self.step % ck_every == 0)
                    )
                    if not boundary:
                        continue
                    n_since = self.step - last_fetch
                    last_fetch = self.step
                    # the boundary metric fetch is the loop's ONE host sync:
                    # any device time the host outran is absorbed here
                    with spans.span("host_sync"), _sync_guard("host_sync"):
                        if self.fault_injector is not None:
                            # drill injection point "sync": a dead peer mid-
                            # collective — the blocking fetch never returns
                            # and the armed watchdog must escape the process
                            # (mode="hang" blocks here)
                            self.fault_injector.maybe_fire("sync", self.step)
                        # the tensorstats packed vectors are ARRAYS — they
                        # ride the same boundary fetch (still the one host
                        # sync) but must bypass the float() coercion and the
                        # scalar sinks (-> ExpManager.log_tensorstats below)
                        last_metrics = {}
                        ts_payload = {}
                        for k, v in metrics.items():
                            if k.startswith(_TS_HIST_PREFIX):
                                ts_payload[k] = np.asarray(v)
                            else:
                                last_metrics[k] = float(v)
                    if monitor is not None:
                        # anomaly policy on the ALREADY-fetched scalars: a
                        # healthy boundary costs one int compare; an anomaly
                        # dumps the forensic bundle and applies the policy
                        action = monitor.check_boundary(self.step, last_metrics)
                        if action == "halt":
                            # do NOT checkpoint: under halt the poisoned
                            # update was applied, and auto-resume must find
                            # the last GOOD checkpoint, not this state
                            halt_reason = (
                                f"health policy=halt: non-finite step "
                                f"{int(last_metrics.get('health/last_nonfinite_step', -1))}"
                            )
                            if control is not None:
                                # folds through the boundary control word
                                # below — every host halts at this step even
                                # if a counter ever diverged across hosts
                                control.request("health_halt", halt_reason)
                            else:
                                logger.error(
                                    "%s (bundle in %s) — stopping without a "
                                    "checkpoint; resume restores the last "
                                    "good save", halt_reason,
                                    self.exp.log_dir,
                                )
                                halted = True
                                self.stop_class = "health_halt"
                    # throughput window excludes validation/checkpoint/compile
                    # wall time (the spans tagged non-productive) so seq/s and
                    # throughput_peak reflect steady-state training only
                    dt = self.exp.step_timed(
                        n_since, exclude_seconds=spans.take_excluded()
                    )
                    last_metrics["step_time"] = dt
                    last_metrics["consumed_samples"] = self.consumed_samples
                    ioc = int(getattr(self.data_module, "io_retry_count", 0)
                              or 0)
                    if ioc:
                        # cumulative transient-read retries the prefetch
                        # thread absorbed (data.io_retries backoff) — a
                        # flaky mount is visible before it becomes a stall
                        last_metrics["data/io_retries"] = float(ioc)
                    if tel.spans:
                        last_metrics.update(
                            {f"time/{k}": v for k, v in spans.drain().items()}
                        )
                    if tel.goodput:
                        last_metrics["goodput_fraction"] = (
                            spans.goodput_fraction())
                    if memplane is not None:
                        # memory/ metrics (worst-device in-use/peak/headroom
                        # + spread) ride the same boundary record into every
                        # sink, the fleet beacon, and the alert rules; the
                        # in-window boundary additionally captures the
                        # memory profile -> memory_summary.json.  With
                        # device_memory ALSO on, the legacy device_* keys
                        # derive from this same sweep — never a second one.
                        mem_metrics = memplane.boundary(self.step)
                        last_metrics.update(mem_metrics)
                        if tel.device_memory:
                            last_metrics.update(
                                _legacy_device_memory_keys(mem_metrics))
                    elif tel.device_memory:
                        last_metrics.update(_device_memory_metrics(self.mesh))
                    if batch_stats is not None and self.step % log_every == 0:
                        # data/ stats the prefetch thread accumulated since
                        # the last LOG boundary.  Drained only when
                        # log_metrics will actually write the record — a
                        # checkpoint/validation boundary off the log cadence
                        # would otherwise reset the accumulator into a
                        # record every sink drops
                        last_metrics.update(batch_stats.drain())
                    # the sinks' writes, after this boundary's drain: the
                    # span lands in the NEXT row's time/log_metrics
                    with spans.span("log_metrics"):
                        self.exp.log_metrics(self.step, last_metrics)
                        if ts_payload:
                            # structured observatory record ->
                            # tensorstats.jsonl (the per-step tensorstats/
                            # SCALARS already rode last_metrics into every
                            # scalar sink above)
                            self.exp.log_tensorstats(self.step, ts_payload)
                    if startup is not None:
                        # the first boundary: the timeline ends with its
                        # host_sync and is written now, so a run killed
                        # after step 1 has it
                        startup_section = self._write_startup(startup)
                        startup = None
                    fleet_metrics: dict[str, float] = {}
                    if fleet is not None:
                        # this host's beacon + (rank 0) the fleet fold; a
                        # newly quiet host dumps a fleet_stall bundle through
                        # the flight recorder, and the returned fleet/*
                        # metrics feed the alert rules below
                        fleet_metrics = fleet.boundary(
                            self.step, last_metrics,
                            spans=(spans.snapshot() if spans.enabled
                                   else None),
                            monitor=monitor,
                        )
                    if alerts is not None:
                        for fire in alerts.observe(
                                self.step,
                                {**last_metrics, **fleet_metrics}):
                            if fire.action == "dump" and monitor is not None:
                                # same forensic machinery as an anomaly:
                                # alert_<step>/ bundle with the ring trail
                                monitor.dump(
                                    self.step, kind="alert",
                                    boundary_metrics=last_metrics,
                                    extra={"alert": fire.to_dict()},
                                )
                            elif fire.action == "halt":
                                # operational halt (state is NOT poisoned):
                                # the graceful-stop path checkpoints for
                                # resume and the reason lands in
                                # run_summary.json (elastic.stop_reason +
                                # the alerts trail)
                                reason = f"alert {fire.rule}: {fire.message}"
                                if control is not None:
                                    # fleet-consistent even on a host-local
                                    # metric: the stop folds through the
                                    # control word at THIS boundary
                                    control.request("alert_halt", reason)
                                else:
                                    _request_stop(reason)
                                    self.stop_class = "alert_halt"
                    ck_now = False
                    fold_stop = False
                    if control is not None:
                        # THE consensus fold (docs/observability.md "Fleet
                        # control"): rank 0 polls control/commands.jsonl,
                        # every host's condition word rides one tiny
                        # replicated collective, and all hosts apply the
                        # SAME decision at this step.  This is the
                        # boundary's only extra cross-host traffic — zero
                        # new syncs between boundaries.  The fold is itself
                        # a blocking rendezvous, so it rides the same hang
                        # guard as the metric fetch: a peer that died
                        # between its host_sync and its fold must not hang
                        # the survivors past the watchdog.
                        with _sync_guard("control_fold"):
                            decision = control.boundary(self.step)
                        if decision.dump and monitor is not None:
                            monitor.dump(
                                self.step, kind="control",
                                boundary_metrics=last_metrics,
                                extra={"control": decision.to_dict()},
                            )
                        ck_now = decision.checkpoint_now
                        if decision.halt:
                            halted = True
                            self.stop_class = "health_halt"
                            logger.error(
                                "control: fleet-consistent halt at step %d "
                                "(%s) — stopping WITHOUT a checkpoint; "
                                "resume restores the last good save",
                                self.step, decision.reason)
                        elif decision.stop:
                            fold_stop = True
                            self.stop_class = decision.conditions[0]
                            if stop_requested["reason"] is None:
                                _request_stop(decision.reason)

                    if halted:
                        break
                    if val_interval and self.step % val_interval == 0 and self.eval_step:
                        with spans.span("validate"):
                            last_metrics["val_loss"] = self.validate(
                                limit_val, detector=detector)
                        self.exp.log_metrics(
                            self.step, {"val_loss": last_metrics["val_loss"]}, force=True
                        )
                    # ONE snapshot of the stop decision for this boundary:
                    # the SIGTERM handler can run at any bytecode (including
                    # inside the cadence save below), and deciding the stop
                    # branch from a re-read would double-save this step —
                    # orbax raises StepAlreadyExistsError.  A notice landing
                    # mid-save stops at the NEXT boundary instead, still
                    # inside the grace window.  Under the control plane the
                    # snapshot is the FOLDED decision, not the raw local
                    # request: a SIGTERM landing after this boundary's fold
                    # must wait for the next fold, or this host would stop
                    # alone while its peers saw an empty word — exactly the
                    # rendezvous mismatch the plane exists to kill.
                    stopping = (fold_stop if control is not None
                                else stop_requested["reason"] is not None)
                    if stopping and self.stop_class is None:
                        r = str(stop_requested["reason"] or "")
                        self.stop_class = (
                            "alert_halt" if r.startswith("alert ")
                            else "max_time" if r.startswith("max_time")
                            else "preemption")
                    if ck_every and self.step % ck_every == 0 and not stopping:
                        with spans.span("checkpoint"):
                            self.save_checkpoint(last_metrics)
                    elif ck_now and not stopping:
                        # operator checkpoint_now (control decision): an
                        # off-cadence save at the deciding boundary — the
                        # cadence branch above already covered an on-cadence
                        # step, and a stop takes the emergency save below
                        with spans.span("checkpoint"):
                            self.save_checkpoint(last_metrics)
                    if stopping:
                        logger.warning(
                            "stopping at step %d: %s — checkpointing for resume",
                            self.step, stop_requested["reason"],
                        )
                        if self.checkpointer is not None:
                            # emergency save: drained inside the grace window
                            # so a background commit failure still counts as
                            # a failed save while retries are possible — it
                            # REPLACES the periodic save even when the stop
                            # step lands on the cadence (an async cadence
                            # save has no drain, no deadline, no guarantee)
                            with spans.span("checkpoint"):
                                self.save_checkpoint(
                                    last_metrics, emergency=True,
                                    deadline=stop_requested["deadline"])
                        break
                if (ck_every and self.checkpointer is not None
                        and stop_requested["reason"] is None and not halted):
                    with spans.span("checkpoint"):
                        self.save_checkpoint(last_metrics)  # final save
                if self.preemption_notice is not None:
                    # a notice that landed during the run's LAST save has no
                    # loop iteration left to convert it: the run is already
                    # complete and checkpointed, so record the fact in the
                    # elastic trail instead of silently dropping it
                    if stop_requested["reason"] is None:
                        stop_requested["reason"] = self.preemption_notice
                    logger.warning(
                        "preemption notice during the final save: run "
                        "already complete (%s)", self.preemption_notice)
                    self.preemption_notice = None
        except BaseException as e:
            fit_exc = e
            if memplane is not None:
                # OOM forensics (telemetry.memory): a RESOURCE_EXHAUSTED
                # escaping the step boundary dumps the oom_<step>/ bundle —
                # last allocator samples, live-buffer attribution, the
                # census's memory_analysis bytes, and the planner's
                # predicted breakdown — before the exception propagates.
                # dump_oom never raises.
                from neuronx_distributed_training_tpu.telemetry.memory import (  # noqa: E501
                    is_oom_error,
                )

                if is_oom_error(e):
                    memplane.dump_oom(
                        self.step, e, boundary_metrics=last_metrics,
                        memory_analysis=self._census_memory_analysis())
            raise
        finally:
            if memplane is not None:
                memplane.close()
            if fleet is not None:
                # final beacon FIRST (before the checkpoint drain can block):
                # clean exit -> closing:true, a raising fit() -> the
                # last_exception record, so the aggregator can tell a dead
                # host from a quiet one.  close() never raises.
                fleet.close(fit_exc, step=self.step)
            if batches is not None:
                batches.close()
            if old_handler is not None:
                import signal as _signal

                _signal.signal(_signal.SIGTERM, old_handler)
            try:
                if self.checkpointer is not None:
                    # the async-save drain: every exit path (clean, halt,
                    # SIGTERM, exception) waits the in-flight commit.  A drain
                    # failure still PROPAGATES (a lost save must be loud) —
                    # the nested finally below just keeps it from eating the
                    # goodput/elastic summaries and exp.close()
                    with spans.span("checkpoint"):
                        self.checkpointer.wait()
                        self.checkpointer.close()
            finally:
                if startup is not None:
                    startup.close()  # no first boundary: no section
                self._write_teardown_summaries(
                    spans, detector, tel, resumed, stop_requested,
                    startup_section)
        return last_metrics

    def _write_startup(self, startup) -> Optional[dict]:
        """The first boundary of the process's first ``fit()``: close the
        start-up timeline, write the ``startup`` section of
        ``run_summary.json`` and say in one line where the time to step 1
        went.  Observability: a failure is logged, never raised."""
        try:
            section = startup.section(_recompile.COMPILES.summary())
            if section is None:
                return None
            self.exp.write_run_summary({"startup": section})
            over = ", ".join(
                f"{k} {v:.1f}" for k, v in section["seconds"].items()
                if v >= 1.0 and k not in ("init_state", "trace_lower"))
            logger.info(
                "start-up: %.1f s from %s to the end of step %d's fetch; "
                "phases over 1 s: %s; compile cache %d hits, %d misses",
                section["to_first_step_s"], section["origin"], self.step,
                over or "none", section["compile_cache"]["cache_hits"],
                section["compile_cache"]["cache_misses"])
            return section
        except Exception as e:  # noqa: BLE001 — observability, not load-bearing
            logger.warning("start-up timeline write failed: %s", e)
            return None
        finally:
            startup.close()

    def _write_teardown_summaries(self, spans, detector, tel, resumed,
                                  stop_requested,
                                  startup_section=None) -> None:
        """fit() teardown after the checkpoint drain: persist the goodput and
        elastic sections of ``run_summary.json`` and close the exp manager.
        Runs even when the drain raised."""
        detector.unwatch_compiles()
        try:
            # beside the census's compile_seconds: every backend compile (or
            # cache read) this fit() saw, with the step it hit
            summary: dict[str, Any] = {
                "compile_events": detector.compile_events}
            if startup_section is not None:
                # the section of the first boundary, completed with the
                # whole run's compiles and cache counters
                summary["startup"] = {
                    **startup_section,
                    **compile_sections(_recompile.COMPILES.summary())}
            if tel.goodput:
                summary["goodput"] = spans.goodput_summary()
                if detector.events:
                    summary["retrace_events"] = detector.events[-20:]
            self.exp.write_run_summary(summary)
        except Exception as e:  # noqa: BLE001 — teardown must finish
            logger.warning("compile/goodput summary write failed: %s", e)
        last_ts = getattr(self.exp, "last_tensorstats", None)
        if last_ts:
            # the final cumulative observatory record — the snapshot
            # tools/quant_readiness.py prices compressed collectives from
            try:
                self.exp.write_run_summary({"tensorstats": last_ts})
            except Exception as e:  # noqa: BLE001 — teardown must finish
                logger.warning("tensorstats summary write failed: %s", e)
        itrail = self._merged_integrity_trail()
        if itrail:
            # the integrity trail (docs/elasticity.md "Integrity &
            # walk-back"): which step actually verified, how many corrupt
            # steps the restore walked past (including at discovery time,
            # before this trainer existed), what got quarantined, and what
            # the post-commit audit cost — metrics_report.py renders it
            try:
                self.exp.write_run_summary({"integrity": itrail})
            except Exception as e:  # noqa: BLE001 — teardown must finish
                logger.warning("integrity summary write failed: %s", e)
        if resumed or self.replan_record is not None \
                or stop_requested["reason"] is not None:
            # the elastic trail (docs/elasticity.md): what the restart
            # cost, whether a replan happened (old plan -> new plan), and
            # why this incarnation stopped — metrics_report.py renders it
            try:
                snap = spans.snapshot()
                section: dict[str, Any] = {
                    "resumed": bool(resumed),
                    "restart_seconds": round(snap.get("restart", 0.0), 3),
                    "replan_seconds": round(snap.get("replan", 0.0), 3),
                }
                if stop_requested["reason"] is not None:
                    section["stop_reason"] = stop_requested["reason"]
                if self.stop_class is not None:
                    # the deciding condition class — trainer.control's
                    # exit-code table maps it to the orchestrator-facing
                    # exit code
                    section["stop_class"] = self.stop_class
                if self.replan_record is not None:
                    section["replan"] = self.replan_record
                self.exp.write_run_summary({"elastic": section})
            except Exception as e:  # noqa: BLE001 — teardown must finish
                logger.warning("elastic summary write failed: %s", e)
        self.exp.close()

    def _merged_integrity_trail(self) -> dict:
        """Union of the discovery-time integrity trail (the replanner's
        walk-back, ``discovery_integrity_trail``) and the checkpointer's own
        restore/audit trail: walk-back counts add, quarantined steps union,
        the restore's verified step wins (it is the step actually used)."""
        disc = dict(self.discovery_integrity_trail or {})
        # getattr: fit() also runs against checkpointer test doubles
        own = dict(getattr(self.checkpointer, "integrity_trail", None) or {})
        if not disc:
            return own
        if not own:
            return disc
        merged = {**disc, **own}
        merged["walk_back_count"] = (int(disc.get("walk_back_count", 0))
                                     + int(own.get("walk_back_count", 0)))
        q = list(disc.get("quarantined_steps") or [])
        for s in own.get("quarantined_steps") or []:
            if s not in q:
                q.append(s)
        merged["quarantined_steps"] = q
        merged["verify_seconds"] = round(
            float(disc.get("verify_seconds", 0.0))
            + float(own.get("verify_seconds", 0.0)), 3)
        if disc.get("legacy_restore") or own.get("legacy_restore"):
            merged["legacy_restore"] = True
        return merged

    def _census_memory_analysis(self) -> Optional[dict]:
        """The compile census's ``memory_analysis`` bytes out of
        ``run_summary.json`` (for the OOM bundle's predicted-vs-actual);
        None when the census didn't run or the file is unreadable."""
        import json as _json
        from pathlib import Path

        try:
            with open(Path(self.exp.log_dir) / "run_summary.json") as f:
                ma = _json.load(f).get("memory_analysis")
            return dict(ma) if isinstance(ma, dict) else None
        except (OSError, ValueError, AttributeError, TypeError):
            return None

    def _compile_census(self, batch, key, spans) -> None:
        """First-compile census (telemetry.compile_census): AOT lower+compile
        the train step, harvest ``memory_analysis()`` bytes / HLO collective
        counts / the analytic FLOPs estimate into ``run_summary.json``, then
        swap the compiled executable into the loop — the census costs ZERO
        extra compiles because the loop runs the very executable it measured.
        Any failure degrades to the plain jit path (observability must never
        kill training)."""
        if not hasattr(self.train_step, "lower"):
            return  # already AOT-compiled, or a test double
        import time as _time

        from neuronx_distributed_training_tpu.telemetry import compile_census

        # deliberately NOT watchdog-guarded: a first compile legitimately runs
        # minutes on TPU, and a sync-tuned timeout would false-abort it
        try:
            t0 = _time.perf_counter()
            # spans.add below has no annotation of its own: name the compile
            # on the profiler's clock and as the open phase here, as
            # SpanTimer.span does
            with named("compile"), shd.collect_trace_facts() as traced:
                lowered = self.train_step.lower(
                    self.params, self.opt_state, batch, key
                )
                compiled = lowered.compile()
            dt = _time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 — census is best-effort
            logger.warning(
                "compile census failed; continuing with the jit path: %s", e
            )
            return
        # the executable is in hand: swap it in BEFORE the fallible harvest/
        # write below — a full run_summary.json disk error must not discard a
        # multi-minute compile and force a second identical one
        self.train_step = compiled
        # compile is non-productive wall time: goodput + the throughput
        # window's exclusion both see it through the span
        spans.add("compile", dt, begin=t0)
        # how the trace partitioned what it could (ops/moe.py:
        # moe_token_shards) and how its flash kernels walk their blocks
        # (ops/flash_attention.py: flash_band) join the run's static facts
        self.run_facts.update(traced)
        for name, value in sorted(traced.items()):
            logger.info("traced: %s %s", name, value)
        if self.run_facts.get("moe_token_shards", 0) is None:
            logger.warning(
                "traced: moe_token_shards unknown (the step's jaxpr was "
                "cached, so the expert block recorded nothing)")
        try:
            census = compile_census(
                compiled,
                compile_seconds=dt,
                flops_per_token=self.run_facts.get("fwd_flops_per_token"),
                extra={k: v for k, v in self.run_facts.items()
                       if k != "fwd_flops_per_token"},
            )
            self.exp.write_run_summary(census)
            logger.info(
                "compile census: %.1fs compile, collectives=%s",
                dt, census.get("collectives"),
            )
        except Exception as e:  # noqa: BLE001 — harvest is best-effort too
            logger.warning(
                "compile census harvest/write failed (the compiled step is "
                "still in use): %s", e
            )
        if self.exp.telemetry.graph_audit:
            self._graph_audit(compiled, lowered)

    def _graph_audit(self, compiled, lowered) -> None:
        """telemetry.graph_audit: run the static contract rules
        (analysis.graph_audit) against the very executable the loop is about
        to train with, attribute every collective to its declared source
        (analysis.graph_contract provenance — an unattributed collective is
        a GSPMD-inserted reshard and flips the verdict), log every finding,
        and persist the verdict to run_summary.json.  Pure host-side HLO
        inspection — no device work, no extra compiles; failures degrade to
        a warning (the audit gates pre-flight in tools/preflight_audit.py
        and tools/graph_contract.py; in-loop it only observes)."""
        try:
            from neuronx_distributed_training_tpu.analysis.graph_audit import (
                AuditContext,
                audit_executable,
            )
            from neuronx_distributed_training_tpu.analysis.graph_contract import (
                attribution_report,
                fingerprint_artifacts,
            )
            from neuronx_distributed_training_tpu.config.loader import (
                batch_schedule,
            )

            ctx = AuditContext(
                cfg=self.cfg, mesh=self.mesh, policy=self.policy,
                model_cfg=self.model_cfg,
                sched=batch_schedule(self.cfg, int(self.mesh.devices.size)),
                donate=self.donate,
                params_tree=self.params, opt_tree=self.opt_state,
                pspecs=self.param_specs, ospecs=self.opt_specs,
            )
            rep = audit_executable(ctx, compiled, lowered,
                                   log=logger.warning)
            summary: dict = {}
            try:
                stablehlo = ""
                if lowered is not None:
                    try:
                        stablehlo = lowered.as_text()
                    except Exception:  # noqa: BLE001 — dtype census degrades
                        pass
                fp = fingerprint_artifacts(ctx, compiled, stablehlo)
                prov = attribution_report(fp)
                for f in prov.findings:
                    logger.warning(f.format())
                rep.extend(prov)
                summary["contract"] = {
                    "collectives": {
                        k: {"count": v["count"], "source": v["source"]}
                        for k, v in fp["collectives"].items()},
                    "collectives_total":
                        prov.stats["collectives_total"],
                    "collectives_unattributed":
                        prov.stats["collectives_unattributed"],
                    "matmul_dtypes": (fp.get("matmul_dtypes") or {}).get(
                        "counts"),
                }
            except Exception as e:  # noqa: BLE001 — provenance is additive
                logger.warning("collective provenance failed: %s", e)
            summary = {**rep.to_dict(), **summary}
            self.exp.write_run_summary({"graph_audit": summary})
        except Exception as e:  # noqa: BLE001 — observability must not kill
            logger.warning("graph audit failed: %s", e)

    def validate(self, limit_batches: int, detector=None) -> float:
        params = self.params
        if (self.ema_cfg is not None
                and self.ema_cfg.evaluate_ema_weights_instead
                and "ema" in self.opt_state):
            # reference evaluate_ema_weights_instead: swap in the averaged
            # weights for validation only
            params = jax.tree_util.tree_map(
                lambda e, p: e.astype(p.dtype), self.opt_state["ema"], self.params
            )
        losses = []
        it = self.val_data_module.sharded_batches(self.mesh)
        for i, batch in enumerate(it):
            if i >= limit_batches:
                break
            if detector is not None:
                detector.check("eval_step", batch)
            m = self.eval_step(params, batch, jax.random.PRNGKey(0))
            losses.append(float(m["val_loss"]))
        return float(np.mean(losses)) if losses else float("nan")

    def save_checkpoint(
        self,
        metrics: Optional[dict[str, float]] = None,
        *,
        emergency: bool = False,
        deadline: Optional[float] = None,
    ) -> None:
        """One checkpoint save: the topology/plan manifest rides along
        (world-size-agnostic resume, trainer.elastic), transient I/O errors
        retry with backoff (``exp_manager.elastic.save_retries``), and
        ``emergency=True`` (the SIGTERM grace window) drains the async commit
        inside the retry loop bounded by ``deadline``."""
        if self.checkpointer is None:
            return
        from neuronx_distributed_training_tpu.trainer.elastic import (
            build_manifest,
        )

        ds = dict(self.cfg.get("distributed_strategy", {}) or {})
        pp = int(ds.get("pipeline_model_parallel_size", 1))
        vp = int(ds.get("virtual_pipeline_model_parallel_size") or 1)
        try:
            manifest = build_manifest(
                self.cfg, self.mesh, step=self.step,
                schedule=self.pipeline_schedule,
                model_family=self.run_facts.get(
                    "model_family", type(self.model_cfg).__name__),
                save_bf16=self.checkpointer.config.save_bf16,
            )
        except Exception as e:  # noqa: BLE001 — a manifest failure must not
            # block the save itself (the checkpoint stays resumable at the
            # SAME world size without one)
            logger.warning("manifest build failed (saving without): %s", e)
            manifest = None
        self.checkpointer.save_with_retry(
            TrainState(
                params=self.params,
                opt_state=self.opt_state,
                step=self.step,
                consumed_samples=self.consumed_samples,
                # authoritative layer layout for converters: VPP training
                # stores layers interleaved [vp, pp, Lc, ...] (ADVICE r2 —
                # converters branch on this, shape sniffing is the fallback)
                extra={"layer_layout": ("interleaved" if pp > 1 and vp > 1
                                        else "flat")},
            ),
            metrics=metrics,
            manifest=manifest,
            force=emergency,
            deadline=deadline,
            drain=emergency,
        )
        if self.fault_injector is not None:
            # drill injection point "save": the save was INITIATED (an async
            # save may be in flight) — the drain-on-teardown contract is what
            # keeps a kill here from orphaning it; sigterm mode is a
            # preemption notice landing mid-save
            if self.fault_injector.maybe_fire("save", self.step):
                self.preemption_notice = (
                    "injected preemption notice (mid-save)")


def assemble_step_program(cfg: ConfigDict, **kw: Any) -> StepProgram:
    """Module-level alias of :meth:`Trainer.assemble` — the entry point the
    static graph auditor (``analysis.graph_audit``) builds on."""
    return Trainer.assemble(cfg, **kw)


def train(cfg: ConfigDict, **kw: Any) -> dict[str, float]:
    """The ``train(cfg)`` entry point (reference ``examples/training.py:41``)."""
    trainer = Trainer.from_config(cfg, **kw)
    return trainer.fit()
