"""Benchmark: Llama-3-8B-shaped pretraining step on one chip.

Prints ONE JSON line: {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}.
The driver-designated metric (BASELINE.json) is Llama-3-8B pretrain MFU with a
north star of >= 45% MFU; vs_baseline is measured_mfu / 45%.

Regimes: the baseline config (reference ``hf_llama3_8B_config.yaml:45-107``)
specifies ``mixed_precision`` (bf16 compute, fp32 master weights + optimizer
state).  That is the headline number when it fits on the chip; the pure-bf16
regime (the reference's bf16+SR) is measured alongside and reported in the same
JSON.  On TPU the model is Llama-3-8B per-layer shapes (hidden 4096 / ffn 14336
/ 32 heads / 8 KV heads / vocab 128256 / seq 8192) with the layer count scaled
to fit one chip — MFU is per-layer-shape-bound, so this measures the same thing
the full 32-layer multi-chip run would.  On CPU it shrinks to a smoke config.

One process owns the device: ``jax.devices()`` is called in-process, once.
With no TPU and no ``--platform cpu`` the run exits non-zero; a failed regime
or sweep raises and the exit code is non-zero too.  No line is emitted for a
measurement that did not happen.  (This file is stripped to that contract;
rebuilding it as a table of cells is ROADMAP S1/S2.)
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(payload: dict) -> None:
    """The single JSON-line emitter.  A headline (metric-shaped) line REFUSES
    to go out without a perf-contract verdict field: every BENCH_*.json line
    must say whether the measurement was checked against the committed
    baseline — ``{"verdict": "no_baseline"}`` is an acceptable answer,
    silence is not (analysis.perf_contract, docs/observability.md)."""
    if "metric" in payload and "perf_contract" not in payload:
        raise RuntimeError(
            "bench: refusing to emit a headline JSON line without a "
            "perf_contract verdict field (populate it via "
            "analysis.perf_contract.bench_verdict — 'no_baseline' counts)"
        )
    print(json.dumps(payload), flush=True)


# the last completed preemption drill (tools/elastic_drill.py writes it);
# when present its restart cost + goodput ride the bench JSON line so fleet
# survivability is visible in the bench trajectory (docs/elasticity.md)
_LAST_DRILL_PATH = "bench_results/last_drill.json"


def _read_repo_json(rel_path: str, default):
    """One loader for the bench_results/*.json snapshots (repo-relative;
    missing/corrupt/non-dict files fall back to ``default``)."""
    import os

    base = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(base, rel_path)) as f:
            out = json.load(f)
        return out if isinstance(out, dict) else default
    except Exception:
        return default


def load_last_drill() -> dict:
    return _read_repo_json(_LAST_DRILL_PATH, {})


def json_float(v, ndigits: int = 4):
    """NaN/Inf-safe JSON scalar: json.dumps would emit bare ``NaN`` (invalid
    JSON) for exactly the diverging runs the health fields exist to flag."""
    import math

    if v is None or not isinstance(v, (int, float)):
        return v
    return round(float(v), ndigits) if math.isfinite(v) else repr(float(v))


def acquire_device(platform: str | None = None):
    """The one in-process ``jax.devices()`` call.  ``platform="cpu"`` is the
    explicit off-hardware smoke mode; otherwise anything but a TPU exits
    non-zero — there is no CPU carry-on for a run that asked for the chip."""
    import jax

    from neuronx_distributed_training_tpu.utils.compile_cache import (
        configure_compilation_cache,
    )

    if platform:
        jax.config.update("jax_platforms", platform)
    configure_compilation_cache()
    dev = jax.devices()[0]
    if platform != "cpu" and dev.platform != "tpu":
        sys.exit(f"bench: no TPU (JAX found {dev.platform} "
                 f"{dev.device_kind}); pass --platform cpu for a CPU smoke run")
    log(f"bench: device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    return dev


def layer_budget(hbm_bytes: int, bytes_per_param: float, *,
                 tied: bool = True, util: float = 0.55) -> int:
    """Estimated deepest Llama-3-8B layer stack fitting ``hbm_bytes``.

    ``util`` is the share of HBM budgeted for parameters and optimizer state;
    the rest is left to activations and temporaries."""
    h, ffn, nh, nkv, vocab = 4096, 14336, 32, 8, 128256
    per_layer = h * (nh + 2 * nkv) * (h // nh) + nh * (h // nh) * h + 3 * h * ffn
    vocab_params = (1 if tied else 2) * vocab * h
    budget_params = hbm_bytes * util / bytes_per_param
    return max(1, min(32, int((budget_params - vocab_params) // per_layer)))


def make_config(llama, on_tpu: bool, attn_impl: str, seq: int, layers: int | None,
                hbm_bytes: int, bytes_per_param: float, *, tied: bool = True,
                block_q: int | None = None, block_kv: int | None = None):
    """Llama-3-8B per-layer shapes, layer count auto-sized to HBM.

    ``tied=True`` is the PINNED bench default (round-3 contract: one config,
    tied embeddings, multi-layer — VERDICT r2): the fp32 master+opt state of
    an untied 1.05B-param vocab pair alone eats ~2/3 of a 16G chip under
    mixed precision."""
    if on_tpu:
        h, ffn, nh, nkv, vocab = 4096, 14336, 32, 8, 128256
        if layers is None:
            layers = layer_budget(hbm_bytes, bytes_per_param, tied=tied)
        # long sequences: the [s, vocab] logits tensor (s*vocab*4B fp32)
        # dominates HBM — switch to the fused chunked head+CE, which never
        # materializes it (fusions.chunked_ce).  Fixed 8 GiB threshold, NOT a
        # fraction of measured HBM: the flagship seq-8192 point (~4.2 GB
        # logits) must always bench un-chunked so runs stay comparable to the
        # recorded baselines regardless of runtime HBM reservation.
        vocab_chunks = 16 if seq * vocab * 4 > 8 * 1024**3 else None
        if vocab_chunks:
            log(f"bench: seq {seq} logits exceed 8 GiB — chunked_ce x{vocab_chunks}")
        return llama.LlamaConfig(
            vocab_size=vocab,
            hidden_size=h,
            intermediate_size=ffn,
            num_layers=layers,
            num_attention_heads=nh,
            num_kv_heads=nkv,
            max_position_embeddings=seq,
            rope_theta=500000.0,
            tie_word_embeddings=tied,
            fuse_qkv=True,
            attention_impl=attn_impl,
            flash_block_q=block_q,
            flash_block_kv=block_kv,
            vocab_chunks=vocab_chunks,
            activations_checkpoint_granularity="selective",
        )
    return llama.LlamaConfig(
        vocab_size=1024,
        hidden_size=256,
        intermediate_size=704,
        num_layers=layers or 1,
        num_attention_heads=8,
        num_kv_heads=4,
        max_position_embeddings=seq,
        attention_impl=attn_impl,
        flash_block_q=block_q,
        flash_block_kv=block_kv,
    )


def run_bench(dev, cfg, policy, seq: int, mbs: int, steps: int, warmup: int,
              num_microbatches: int = 1, trace: bool = False,
              tensorstats: bool = False) -> dict:
    """One timed regime run; returns {ms_per_step, tokens_per_sec, mfu}.

    ``mbs`` is the TOTAL rows per step; ``num_microbatches > 1`` runs the
    trainer's real grad-accumulation scan (one optimizer update per step),
    which is what the autotune cost model prices — the plan-topk sweep
    passes it so predicted and measured steps are the same unit.
    ``trace=True`` additionally captures a short device-time trace window
    AFTER the timed loop (so profiling overhead never contaminates
    ms_per_step) and reports measured achieved_overlap /
    exposed_collective_seconds (telemetry.trace_analysis).
    ``tensorstats=True`` rides the in-graph tensor-numerics plane
    (telemetry.tensorstats) on the same compiled step and attaches a compact
    per-collective-class quant-readiness summary to the JSON line (joined
    with the trace's measured exposed seconds when ``trace`` is also on)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.optim.adamw import (
        AdamWConfig, init_opt_state, opt_state_specs,
    )
    from neuronx_distributed_training_tpu.optim.lr import constant_lr
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from neuronx_distributed_training_tpu.telemetry import HealthConfig
    from neuronx_distributed_training_tpu.trainer.step import (
        jit_train_step, make_train_step,
    )
    from neuronx_distributed_training_tpu.utils import perf

    mesh = build_mesh(MeshConfig(), devices=[dev])
    pspecs = llama.param_specs(cfg)
    with mesh, shd.use_mesh(mesh):
        params = llama.init_params(jax.random.PRNGKey(0), cfg, policy)
        ns = functools.partial(NamedSharding, mesh)
        put = lambda tree, specs: jax.device_put(
            tree, jax.tree_util.tree_map(ns, specs, is_leaf=lambda x: isinstance(x, P))
        )
        params = put(params, pspecs)
        # numerics health rides the bench step exactly as it rides the
        # trainer's (telemetry.health): the in-graph finiteness counters let
        # the JSON line distinguish a fast-but-diverging run (nonfinite
        # steps, exploding final grad norm) from a healthy one
        # param_norm off: bench never reports it, and the full-parameter
        # norm reduction would sit inside the timed loop skewing ms_per_step
        health = HealthConfig(enabled=True, policy="dump_and_continue",
                              param_norm=False)
        ts_cfg = None
        if tensorstats:
            from neuronx_distributed_training_tpu.telemetry import (
                TensorStatsConfig,
            )

            ts_cfg = TensorStatsConfig(enabled=True)
        opt_state = init_opt_state(params, policy, health=True,
                                   tensorstats=ts_cfg)
        ospecs = opt_state_specs(params, pspecs, mesh, zero1=True, policy=policy,
                                 health=True, tensorstats=ts_cfg)
        opt_state = put(opt_state, ospecs)

        def loss_fn(p, batch, step_key):
            return llama.forward(p, batch, cfg, policy)

        step = make_train_step(loss_fn, AdamWConfig(), constant_lr(1e-4), policy,
                               num_microbatches=num_microbatches,
                               param_specs=pspecs, health_cfg=health,
                               tensorstats_cfg=ts_cfg)
        jstep = jit_train_step(step, mesh, pspecs, ospecs)

        ids = jax.random.randint(
            jax.random.PRNGKey(1), (mbs, seq), 0, cfg.vocab_size, dtype=jnp.int32
        )
        batch = {"input_ids": ids, "labels": ids}
        batch = jax.device_put(batch, ns(P(("data", "expert"))))
        key = jax.random.PRNGKey(2)

        # AOT compile first so the bench reports the trainer's telemetry
        # schema (compile_seconds + collective/memory census) and the timed
        # loop runs the very executable that was measured — zero extra
        # compiles (telemetry.census, same flow as Trainer._compile_census).
        from neuronx_distributed_training_tpu.telemetry import compile_census

        t_compile = time.perf_counter()
        lowered = jstep.lower(params, opt_state, batch, key)
        compiled = lowered.compile()
        compile_seconds = time.perf_counter() - t_compile
        census = compile_census(compiled, compile_seconds=compile_seconds)
        log(f"bench: compiled in {compile_seconds:.1f}s "
            f"collectives={census.get('collectives')}")

        # pre-flight graph audit of the very executable being measured
        # (analysis.graph_audit): a bench number from a step that silently
        # lost donation (or grew a stray collective) is not comparable to
        # the recorded baselines — the verdict rides the JSON line
        audit_summary = None
        try:
            from neuronx_distributed_training_tpu.analysis.graph_audit import (
                AuditContext, audit_executable,
            )

            ctx = AuditContext(
                cfg={"distributed_strategy": {"zero1": True}}, mesh=mesh,
                policy=policy, model_cfg=cfg,
                sched={"global_batch_size": mbs, "micro_batch_size": mbs},
                donate=True, params_tree=params, opt_tree=opt_state,
                pspecs=pspecs, ospecs=ospecs,
            )
            audit = audit_executable(
                ctx, compiled, lowered, log=lambda m: log(f"bench: {m}"))
            audit_summary = audit.summary()
        except Exception as e:  # noqa: BLE001 — audit must never fail the bench
            log(f"bench: graph audit unavailable: {e}")

        t_warm = time.perf_counter()
        for _ in range(warmup):
            params, opt_state, metrics = compiled(params, opt_state, batch, key)
        jax.block_until_ready(metrics)
        log(f"bench: warmup done in {time.perf_counter() - t_warm:.1f}s "
            f"loss={float(metrics['loss']):.4f}")

        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, metrics = compiled(params, opt_state, batch, key)
        # each step consumes the one before (params, optimizer state), so
        # the last step's metrics being ready means the whole chain ran
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - t0
        # health counters: fetched AFTER the fence, outside the timed window
        nonfinite_steps = int(metrics["health/nonfinite_count"])
        skipped_updates = int(metrics["health/skipped_count"])
        final_grad_norm = float(metrics["grad_norm"])
        if nonfinite_steps:
            log(f"bench: WARNING {nonfinite_steps} non-finite steps — the "
                f"throughput number is for a DIVERGING run")
        dt = elapsed / steps

        # optional device-time trace window, AFTER the timed loop: measured
        # compute/comms overlap for the very executable just benchmarked
        trace_summary = None
        if trace:
            import tempfile

            from neuronx_distributed_training_tpu.telemetry.trace import (
                trace_steps,
            )

            def _traced_step(i):
                nonlocal params, opt_state, metrics
                params, opt_state, metrics = compiled(
                    params, opt_state, batch, key)
                _ = float(metrics["loss"])  # flush so the trace sees the step

            try:
                trace_summary = trace_steps(
                    _traced_step, min(3, max(steps, 1)),
                    tempfile.mkdtemp(prefix="nxdt_bench_trace_"))
            except Exception as e:  # noqa: BLE001 — trace must not fail the bench
                log(f"bench: trace capture failed: {e}")
            if trace_summary is not None:
                log(f"bench: trace achieved_overlap="
                    f"{trace_summary.get('achieved_overlap')} "
                    f"exposed_collective_seconds="
                    f"{trace_summary.get('exposed_collective_seconds')}")

        # quant-readiness: decode the streamed dynamic-range histograms
        # (fetched AFTER the fence, outside the timed window) and simulate
        # block-scaled int8 per collective class — compact enough to ride
        # the JSON line; tools/quant_readiness.py renders the full report
        quant_readiness = None
        if ts_cfg is not None:
            try:
                import numpy as np

                from neuronx_distributed_training_tpu.telemetry.quant_readiness import (  # noqa: E501
                    build_report,
                )
                from neuronx_distributed_training_tpu.telemetry.tensorstats import (  # noqa: E501
                    HIST_PREFIX, decode_cum,
                )

                groups = {
                    k[len(HIST_PREFIX):]: decode_cum(
                        np.asarray(v).tolist(), ts_cfg)
                    for k, v in metrics.items() if k.startswith(HIST_PREFIX)
                }
                rep = build_report(
                    {"step": steps, "groups": groups},
                    overlap_by_class=(trace_summary or {}).get(
                        "overlap_by_class"))
                best = str(rep["block_sizes"][-1])
                quant_readiness = {}
                for kind in rep["ranking"]:
                    e = rep["classes"][kind]
                    if "pooled" not in e \
                            and e.get("predicted_seconds_saved") is None:
                        continue
                    p = e.get("pooled", {}).get(best, {})
                    quant_readiness[kind] = {
                        "block_size": int(best),
                        "sqnr_db": json_float(p.get("sqnr_db")),
                        "rel_error_rms": json_float(
                            p.get("rel_error_rms"), 9),
                        "bytes_saved_frac": json_float(
                            e.get("bytes_saved_frac"), 6),
                        "predicted_seconds_saved": json_float(
                            e.get("predicted_seconds_saved"), 9),
                    }
            except Exception as e:  # noqa: BLE001 — telemetry must not fail the bench
                log(f"bench: quant-readiness summary unavailable: {e}")

    # measured peak HBM (telemetry.memory): the allocator's live watermark
    # after the timed loop when the backend reports one, else the compiled
    # memory_analysis() static estimate — the source is named so a reader
    # never mistakes a static bound for a live measurement
    peak_hbm_bytes = None
    hbm_headroom_fraction = None
    peak_hbm_source = None
    try:
        from neuronx_distributed_training_tpu.telemetry.memory import (
            device_memory_samples, memory_metrics,
        )

        mm = memory_metrics(device_memory_samples([dev]))
        peak_hbm_bytes = mm.get("memory/peak_bytes_max") \
            or mm.get("memory/bytes_in_use_max")
        hbm_headroom_fraction = mm.get("memory/hbm_headroom_fraction")
        if peak_hbm_bytes is not None:
            peak_hbm_source = "memory_stats"
    except Exception as e:  # noqa: BLE001 — sampling must not fail the bench
        log(f"bench: allocator sampling unavailable: {e}")
    if peak_hbm_bytes is None:
        ma = census.get("memory_analysis") or {}
        if ma.get("peak_bytes"):
            peak_hbm_bytes = float(ma["peak_bytes"])
            peak_hbm_source = "memory_analysis"

    tokens_per_sec = mbs * seq / dt
    fwd_ft = perf.flops_for_config(cfg, seq)
    step_ft = perf.train_step_flops_per_token(fwd_ft)
    # no peak off the TPU, so no MFU for a --platform cpu smoke run
    peak = perf.detect_peak_tflops(dev)
    mfu = perf.mfu(tokens_per_sec, step_ft, peak) if peak else None
    log(f"bench: {dt * 1e3:.1f} ms/step, {tokens_per_sec:,.0f} tok/s/chip, "
        + (f"MFU {100 * mfu:.1f}% (peak {peak} TF)" if peak else
           "no MFU (not a TPU)"))
    out = {
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": mfu,
        "peak_tflops": peak,
        "num_layers": cfg.num_layers,
        # trainer-telemetry-schema fields (run_summary.json parity) so the
        # BENCH_*.json trajectory is comparable with training runs
        "compile_seconds": round(compile_seconds, 2),
        "collectives": census.get("collectives"),
        "memory_analysis": census.get("memory_analysis"),
        # measured memory (telemetry.memory / analysis.perf_contract PC501):
        # worst-device peak bytes + remaining headroom fraction
        "peak_hbm_bytes": json_float(peak_hbm_bytes, 1),
        "hbm_headroom_fraction": json_float(hbm_headroom_fraction, 4),
        "peak_hbm_source": peak_hbm_source,
        # numerics-health fields (telemetry.health): a throughput line from a
        # diverging run must be distinguishable from a healthy one
        "nonfinite_steps": nonfinite_steps,
        "skipped_updates": skipped_updates,
        "final_grad_norm": json_float(final_grad_norm),
        # pre-flight graph-audit verdict (rule hits by severity + donation
        # coverage) for the measured executable
        "graph_audit": audit_summary,
    }
    if quant_readiness is not None:
        # compact per-collective-class compression verdict (--tensorstats):
        # predicted SQNR / bytes saved at the largest simulated block size
        out["quant_readiness"] = quant_readiness
    if trace_summary is not None:
        # measured device-time facts (--trace): the achieved-overlap signal
        # the autotune cost model calibrates against
        out.update({
            "achieved_overlap": json_float(
                trace_summary.get("achieved_overlap"), 6),
            "exposed_collective_seconds": json_float(
                trace_summary.get("exposed_collective_seconds"), 6),
            "collective_seconds": json_float(
                trace_summary.get("collective_seconds"), 6),
            "overlap_by_class": {
                k: json_float(v.get("achieved_overlap"), 4)
                for k, v in (trace_summary.get("overlap_by_class")
                             or {}).items()
            },
        })
    return out


def plan_topk_measure(dev, base_cfg, policy, precision_block, seq: int,
                      mbs: int, steps: int, warmup: int, topk: int) -> dict:
    """Measure the autotune planner's top-N plans for the bench workload and
    score predicted-vs-measured rank agreement (Kendall tau).

    The single-chip lattice varies remat policy (and microbatch count when
    gbs allows), so this is a true end-to-end test of the cost model's
    compute/memory terms: every bench run that passes ``--plan-topk``
    appends a fresh calibration point to the JSON record.  A plan that
    fails to run (e.g. remat=none OOM) is recorded with ``measured_ms:
    null`` and excluded from tau."""
    import dataclasses

    from neuronx_distributed_training_tpu.autotune import (
        kendall_tau,
        plan_config,
    )

    raw = {
        "name": "bench", "model_source": "hf",
        "trainer": {"max_steps": 1},
        "distributed_strategy": {"tensor_model_parallel_size": 1,
                                 "zero1": True},
        "data": {"seq_length": seq, "global_batch_size": mbs,
                 "micro_batch_size": mbs, "synthetic": True},
        "model": {
            "architecture": "llama",
            "vocab_size": base_cfg.vocab_size,
            "hidden_size": base_cfg.hidden_size,
            "intermediate_size": base_cfg.intermediate_size,
            "num_layers": base_cfg.num_layers,
            "num_attention_heads": base_cfg.num_attention_heads,
            "num_key_value_heads": base_cfg.num_kv_heads,
            "max_position_embeddings": seq,
            "tie_word_embeddings": base_cfg.tie_word_embeddings,
            "activations_checkpoint_granularity":
                base_cfg.activations_checkpoint_granularity,
        },
        "precision": precision_block,
    }
    report = plan_config(raw, chips=1, audit=False, top_k=topk)
    rows = []
    predicted, measured = [], []
    for cand in report.candidates[:topk]:
        plan = cand.plan
        cfg_i = dataclasses.replace(
            base_cfg,
            activations_checkpoint_granularity=(
                None if plan.remat == "none" else plan.remat),
        )
        from neuronx_distributed_training_tpu.parallel.pipeline import (
            predicted_bubble_fraction,
        )

        row = {"plan": plan.describe(),
               "predicted_ms": round(cand.estimate.step_seconds * 1e3, 2),
               "predicted_hbm_gb": round(cand.estimate.hbm_bytes / 1024**3,
                                         3),
               "bubble_fraction_predicted": round(predicted_bubble_fraction(
                   plan.schedule, plan.pp, plan.num_microbatches, plan.vp), 6),
               "measured_ms": None}
        try:
            # measure the SAME unit the estimate prices: all nm microbatches
            # through the trainer's grad-accumulation scan with ONE
            # optimizer update (naive per-microbatch scaling would count nm
            # updates and bias the tau against small-mbs plans)
            r = run_bench(dev, cfg_i, policy, seq, mbs, steps, warmup,
                          num_microbatches=plan.num_microbatches)
            row["measured_ms"] = r["ms_per_step"]
            # measured memory beside the residual record: the per-plan
            # predicted-vs-measured HBM pair is a calibration point for the
            # cost model's transient constants (telemetry.memory)
            row["peak_hbm_bytes"] = r.get("peak_hbm_bytes")
            row["hbm_headroom_fraction"] = r.get("hbm_headroom_fraction")
            predicted.append(cand.estimate.step_seconds * 1e3)
            measured.append(r["ms_per_step"])
            # per-term predicted-vs-measured residuals: the cost model
            # audited against this benched plan (analysis.perf_contract;
            # comms/bubble terms stay None unless a trace/timeline measured
            # them — the audit never pretends)
            try:
                from neuronx_distributed_training_tpu.analysis.perf_contract import (  # noqa: E501
                    residual_report,
                )

                row["residuals"] = residual_report(
                    cand.estimate.to_dict(),
                    {"step_seconds": r["ms_per_step"] / 1e3,
                     "exposed_collective_seconds": r.get(
                         "exposed_collective_seconds"),
                     "bubble_fraction_measured": r.get(
                         "bubble_fraction_measured")})
            except Exception as e:  # noqa: BLE001 — residuals are advisory
                log(f"bench: residual report unavailable: {e}")
        except Exception as e:  # noqa: BLE001 — one failed plan must not
            # kill the sweep (and its failure is itself signal)
            row["error"] = f"{type(e).__name__}: {e}"[:300]
            log(f"bench: plan-topk candidate failed: {row['error']}")
        rows.append(row)
    tau = kendall_tau(predicted, measured)
    return {
        "plans": rows,
        "kendall_tau": json_float(tau) if tau is not None else None,
        "n_measured": len(measured),
    }


def schedule_sweep(steps: int, warmup: int, *, pp: int = 2, nm: int = 16,
                   vp: int = 2, trace: bool = True) -> dict:
    """Measure ALL FOUR pipeline schedules on one fixed tiny mesh and emit
    per-schedule ``{ms_per_step, bubble_fraction_measured,
    bubble_fraction_predicted, residual}`` rows — the one-command
    reproduction of the work-compacted executor's wall-clock claim
    (interleaved <= plain 1f1b at pp=2/nm=16/vp=2, the exact point the old
    lockstep executor lost by ~1.25x).

    The mesh is ``pipe=pp`` over every visible device (8 virtual CPU
    devices under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
    real chips on hardware).  Every schedule runs the SAME flat layer
    stack (reshaped ``to_interleaved`` for vp>1) at identical per-step
    FLOPs, so the rows are directly comparable; each row also captures a
    short device-time trace window AFTER its timed loop and reports the
    timeline-measured bubble fraction beside the table's prediction
    (``analysis.perf_contract`` gates PC302 per row and the
    interleaved-vs-1f1b ordering as PC303)."""
    import functools as _ft

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.parallel.mesh import (
        MeshConfig, build_mesh,
    )
    from neuronx_distributed_training_tpu.parallel.pipeline import (
        MANUAL_VJP_SCHEDULES,
        pipeline_loss,
        pipeline_loss_and_grad,
        predicted_bubble_fraction,
        to_interleaved,
        work_table,
    )
    from neuronx_distributed_training_tpu.telemetry.step_timeline import (
        pipeline_facts,
    )
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

    n_dev = len(jax.devices())
    if n_dev < pp or n_dev % pp:
        raise RuntimeError(
            f"--schedule-sweep needs a device count divisible by pp={pp} "
            f"(got {n_dev}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            f"jax imports)")

    policy = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                         softmax_dtype=jnp.float32)
    mb, seq = max(4, n_dev // pp), 64
    cfg = llama.LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_layers=2 * pp * vp, num_attention_heads=4, num_kv_heads=2,
        max_position_embeddings=seq,
        activations_checkpoint_granularity=None,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg, policy)
    ids = jax.random.randint(jax.random.PRNGKey(1), (nm, mb, seq), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    mbs = {"input_ids": ids, "labels": ids}
    embed_fn, stage_fn, loss_fn = llama.pipeline_hooks(cfg, policy)
    hh, hp_of, hw_of, _fold = llama.onef1b_head_hooks(cfg, policy)

    def sharded(mesh, schedule_vp):
        specs = llama.param_specs(cfg, pipeline=True)
        p = params
        if schedule_vp > 1:
            p = {**p, "layers": to_interleaved(p["layers"], pp, schedule_vp)}
            specs = dict(specs)
            specs["layers"] = jax.tree_util.tree_map(
                lambda sp: P(None, sp[0], None, *tuple(sp)[1:]),
                specs["layers"], is_leaf=lambda x: isinstance(x, P))
        ns = _ft.partial(NamedSharding, mesh)
        shp = jax.device_put(p, jax.tree_util.tree_map(
            ns, specs, is_leaf=lambda x: isinstance(x, P)))
        shm = jax.device_put(mbs, ns(P(None, ("data", "expert"))))
        return shp, shm

    def loss_and_grad(mesh, schedule, schedule_vp):
        if schedule == "wavefront":
            def fn(p, m):
                return jax.value_and_grad(
                    lambda p_, m_: pipeline_loss(
                        p_, p_["layers"], m_, embed_fn=embed_fn,
                        stage_fn=stage_fn, loss_fn=loss_fn, mesh=mesh,
                        virtual_pipeline_size=schedule_vp))(p, m)
        else:
            def fn(p, m):
                return pipeline_loss_and_grad(
                    p, p["layers"], m, embed_fn=embed_fn, stage_fn=stage_fn,
                    head_hidden_fn=hh, head_params=hp_of(p),
                    head_weight=hw_of(p), mesh=mesh,
                    virtual_pipeline_size=schedule_vp,
                    zero_bubble=(schedule == "1f1b-zb"))
        return fn

    # wavefront measures at the SAME vp as the interleave (identical layer
    # layout and circular schedule — the apples-to-apples memory rival)
    matrix = [("wavefront", vp), ("1f1b", 1), ("1f1b-interleaved", vp),
              ("1f1b-zb", 1)]
    rows = []
    for schedule, svp in matrix:
        mesh = build_mesh(MeshConfig(
            pipeline_model_parallel_size=pp,
            virtual_pipeline_model_parallel_size=svp))
        shp, shm = sharded(mesh, svp)
        fn = loss_and_grad(mesh, schedule, svp)
        row = {"schedule": schedule, "pp": pp, "nm": nm, "vp": svp,
               "bubble_fraction_predicted": round(
                   predicted_bubble_fraction(schedule, pp, nm, svp), 6)}
        with mesh, shd.use_mesh(mesh):
            jfn = jax.jit(fn)
            t_c = time.perf_counter()
            out = jfn(shp, shm)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            row["compile_seconds"] = round(time.perf_counter() - t_c, 2)
            for _ in range(warmup):
                out = jfn(shp, shm)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = jfn(shp, shm)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            row["ms_per_step"] = round(
                (time.perf_counter() - t0) / max(steps, 1) * 1e3, 2)
            loss = out[0]
            row["loss"] = json_float(float(loss), 5)
            if trace:
                import tempfile

                from neuronx_distributed_training_tpu.telemetry.trace import (
                    trace_steps,
                )

                def _step(i):
                    o = jfn(shp, shm)
                    # fence on the loss scalar only: a full-tree fetch
                    # would put host time inside the annotation window and
                    # inflate the measured idle
                    o[0].block_until_ready()

                ticks = (work_table(schedule, pp, nm, svp).tick_counts()
                         if schedule in MANUAL_VJP_SCHEDULES else None)
                try:
                    summary = trace_steps(
                        _step, 2,
                        tempfile.mkdtemp(prefix="nxdt_sweep_trace_"),
                        pipeline=pipeline_facts(
                            schedule, pp, nm, svp,
                            row["bubble_fraction_predicted"],
                            ticks_per_step=ticks))
                except Exception as e:  # noqa: BLE001 — one schedule's
                    # trace failure must not kill the sweep
                    summary = None
                    log(f"bench: sweep trace failed for {schedule}: {e}")
                pipe = (summary or {}).get("pipeline") or {}
                row["bubble_fraction_measured"] = json_float(
                    pipe.get("bubble_fraction_measured"), 6)
                row["bubble_residual"] = json_float(
                    pipe.get("bubble_residual"), 6)
                row["ticks_detected"] = pipe.get("ticks_detected")
        log(f"bench[sweep] {schedule:<17} {row['ms_per_step']:>8.2f} ms/step"
            f"  predicted_bubble={row['bubble_fraction_predicted']:.4f}"
            f"  measured={row.get('bubble_fraction_measured')}")
        rows.append(row)

    by_sched = {r["schedule"]: r for r in rows}
    ratio = None
    if by_sched.get("1f1b", {}).get("ms_per_step"):
        ratio = round(by_sched["1f1b-interleaved"]["ms_per_step"]
                      / by_sched["1f1b"]["ms_per_step"], 4)
    return {
        "rows": rows,
        "pp": pp, "nm": nm, "vp": vp,
        "micro_batch": mb, "seq_len": seq, "num_layers": cfg.num_layers,
        "interleaved_over_1f1b": ratio,
    }


def overlap_sweep(steps: int, warmup: int, *, trace: bool = True) -> dict:
    """Measure the engineered-overlap claim end to end: the SAME tiny
    dp-only ZeRO-1 training step at three ``distributed_strategy.overlap``
    settings — monolithic (``off``), one combined bucket (``bucketed-1``),
    and per-layer-group buckets (``bucketed-N``) — and emit per-variant
    ``{ms_per_step, exposed_collective_seconds, achieved overlap by class}``
    rows from a device-time trace window.

    Each variant goes through the REAL trainer assembly
    (``trainer.loop.assemble_step_program``): the bucket plan, the prefetch
    barrier chain, and the jitted step are exactly what a training run gets
    — nothing here is a bench-only reimplementation.  All variants share
    seed/model/data, so their losses must agree (reported per row; the
    parity matrix in tests/test_overlap.py pins it bitwise-level at
    tolerance).  ``analysis.perf_contract`` gates the ordering (PC203:
    bucketed exposed collective seconds at or below monolithic) and the
    committed ``<device>_overlap_sweep`` baseline ratchets per-row drift."""
    import functools as _ft

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.optim.adamw import init_opt_state
    from neuronx_distributed_training_tpu.optim.overlap import (
        build_bucket_plan,
    )
    from neuronx_distributed_training_tpu.parallel import sharding as shd
    from neuronx_distributed_training_tpu.telemetry.health import (
        grad_group_of,
    )
    from neuronx_distributed_training_tpu.trainer.loop import (
        assemble_step_program,
    )

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"--overlap-sweep needs >= 2 devices for dp collectives (got "
            f"{n_dev}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            f"jax imports)")

    seq, gbs = 128, n_dev
    base = {
        "name": "overlap_sweep",
        "model_source": "hf",
        "seed": 0,
        "trainer": {"max_steps": max(steps, 2)},
        "distributed_strategy": {"zero1": True},
        "data": {"seq_length": seq, "global_batch_size": gbs,
                 "micro_batch_size": 1, "synthetic": True},
        "model": {
            "architecture": "llama", "vocab_size": 2048,
            "hidden_size": 256, "intermediate_size": 512, "num_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "max_position_embeddings": seq,
            "optim": {"name": "adamw_fp32OptState", "lr": 1.0e-3,
                      "sched": {"name": "CosineAnnealing",
                                "warmup_steps": 2,
                                "max_steps": max(steps, 2)}},
        },
        "precision": {"type": "mixed_precision"},
    }
    # one combined bucket vs a bucket per layer group: the huge size
    # coalesces everything, the tiny size closes a bucket at every
    # grad_group_of boundary
    variants = [("off", None), ("bucketed-1", 1024.0), ("bucketed-N", 1e-6)]

    import numpy as _np

    ids = _np.random.default_rng(0).integers(
        0, base["model"]["vocab_size"], (gbs, seq), dtype=_np.int32)

    rows = []
    for variant, bucket_mb in variants:
        cfg_doc = json.loads(json.dumps(base))
        if bucket_mb is not None:
            cfg_doc["distributed_strategy"]["overlap"] = {
                "zero1_bucket_mb": bucket_mb, "prefetch_ag": True}
        cfg = load_config(cfg_doc)
        asm = assemble_step_program(cfg, build_data=False)
        mesh = asm.mesh
        ns = _ft.partial(NamedSharding, mesh)
        shardings = lambda specs: jax.tree_util.tree_map(  # noqa: E731
            ns, specs, is_leaf=lambda x: isinstance(x, P))
        row = {"variant": variant,
               "bucket_mb": bucket_mb, "n_buckets": 0}
        if bucket_mb is not None:
            plan = build_bucket_plan(
                asm.abstract_params, asm.pspecs, asm.ospecs["mu"], mesh,
                bucket_mb=bucket_mb, group_fn=grad_group_of)
            row["n_buckets"] = len(plan.buckets) if plan else 0
        with mesh, shd.use_mesh(mesh):
            params = jax.jit(asm.param_builder,
                             out_shardings=shardings(asm.pspecs))(asm.init_key)
            opt_state = jax.jit(
                _ft.partial(init_opt_state, policy=asm.policy,
                            ema=asm.ema_cfg is not None,
                            health=getattr(asm.health_cfg, "enabled", False)),
                out_shardings=shardings(asm.ospecs))(params)
            batch = jax.device_put(
                {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)},
                ns(P(("data", "expert"))))
            key = jax.random.PRNGKey(7)
            jstep = asm.jstep
            t_c = time.perf_counter()
            params, opt_state, metrics = jstep(params, opt_state, batch, key)
            metrics["loss"].block_until_ready()
            row["compile_seconds"] = round(time.perf_counter() - t_c, 2)
            for _ in range(warmup):
                params, opt_state, metrics = jstep(params, opt_state, batch,
                                                   key)
            metrics["loss"].block_until_ready()
            t0 = time.perf_counter()
            for _ in range(steps):
                params, opt_state, metrics = jstep(params, opt_state, batch,
                                                   key)
            metrics["loss"].block_until_ready()
            row["ms_per_step"] = round(
                (time.perf_counter() - t0) / max(steps, 1) * 1e3, 2)
            row["loss"] = json_float(float(metrics["loss"]), 5)
            if trace:
                import tempfile

                from neuronx_distributed_training_tpu.telemetry.trace import (
                    trace_steps,
                )

                def _step(i):
                    nonlocal params, opt_state, metrics
                    params, opt_state, metrics = jstep(params, opt_state,
                                                       batch, key)
                    metrics["loss"].block_until_ready()

                try:
                    # 3 traced steps: per-step collective timings on the
                    # virtual-CPU mesh jitter with host scheduling, and the
                    # PC203 ordering gate needs the averaging
                    summary = trace_steps(
                        _step, 3, tempfile.mkdtemp(prefix="nxdt_ov_trace_"))
                except Exception as e:  # noqa: BLE001 — one variant's trace
                    # failure must not kill the sweep
                    summary = None
                    log(f"bench: overlap trace failed for {variant}: {e}")
                summary = summary or {}
                row["exposed_collective_seconds"] = json_float(
                    summary.get("exposed_collective_seconds"), 9)
                row["collective_seconds"] = json_float(
                    summary.get("collective_seconds"), 9)
                row["achieved_overlap"] = json_float(
                    summary.get("achieved_overlap"), 6)
                row["overlap_by_class"] = summary.get("overlap_by_class") or {}
        log(f"bench[overlap] {variant:<11} buckets={row['n_buckets']:<2} "
            f"{row['ms_per_step']:>8.2f} ms/step  "
            f"exposed={row.get('exposed_collective_seconds')}s")
        rows.append(row)

    by_var = {r["variant"]: r for r in rows}
    ratio = None
    off_exp = (by_var.get("off") or {}).get("exposed_collective_seconds")
    bn_exp = (by_var.get("bucketed-N") or {}).get(
        "exposed_collective_seconds")
    if off_exp and bn_exp is not None:
        ratio = round(bn_exp / off_exp, 4)
    return {
        "rows": rows,
        "dp": n_dev, "seq_len": seq, "global_batch": gbs,
        "num_layers": base["model"]["num_layers"],
        "bucketed_over_off_exposed": ratio,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--mbs", type=int, default=1)
    ap.add_argument("--attn", choices=["auto", "core", "flash"], default="auto")
    ap.add_argument("--block-q", type=int, default=None,
                    help="flash tile override (per-chip tuning sweep)")
    ap.add_argument("--block-kv", type=int, default=None)
    ap.add_argument("--regime", choices=["both", "mixed", "bf16"], default="both")
    ap.add_argument("--remat", choices=["selective", "full", "none"],
                    default="selective",
                    help="activation-checkpoint granularity for the bench "
                         "model (perf experiment knob)")
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="force a platform (cpu for local smoke runs)")
    ap.add_argument("--untied", action="store_true",
                    help="untie embeddings/head (off the pinned bench config; "
                         "for comparison runs only)")
    ap.add_argument("--plan-topk", type=int, default=0, metavar="N",
                    help="additionally MEASURE the autotune planner's top-N "
                         "single-chip plans (remat/microbatch lattice) and "
                         "record predicted-vs-measured rank agreement "
                         "(Kendall tau) in the JSON line — every bench run "
                         "scores the cost model")
    ap.add_argument("--trace", action="store_true",
                    help="capture a short device-time trace window AFTER "
                         "the timed loop (telemetry.trace) and emit the "
                         "measured achieved_overlap / "
                         "exposed_collective_seconds in the JSON line — "
                         "the signal the autotune cost model's comms term "
                         "calibrates against")
    ap.add_argument("--tensorstats", action="store_true",
                    help="ride the in-graph tensor-numerics plane "
                         "(telemetry.tensorstats) on the bench step and "
                         "emit a compact per-collective-class "
                         "quant-readiness summary in the JSON line "
                         "(predicted SQNR / bytes saved for block-scaled "
                         "int8; combine with --trace to price the savings "
                         "in measured exposed seconds)")
    ap.add_argument("--contract-key", default=None, metavar="NAME",
                    help="perf-contract baseline key override (default: "
                         "derived from the device identity, e.g. cpu_bench "
                         "— analysis/perf_baselines/<key>.json)")
    ap.add_argument("--schedule-sweep", action="store_true",
                    help="measure ALL FOUR pipeline schedules (wavefront, "
                         "1f1b, 1f1b-interleaved, 1f1b-zb) on a fixed tiny "
                         "pp=2/nm=16/vp=2 mesh and emit per-schedule "
                         "{ms_per_step, bubble_fraction_measured/predicted, "
                         "residual} rows in the JSON line — the one-command "
                         "reproduction of the work-compacted executor's "
                         "wall-clock ordering (runs INSTEAD of the headline "
                         "single-chip bench)")
    ap.add_argument("--overlap-sweep", action="store_true",
                    help="measure the engineered-overlap claim: the same "
                         "dp-only ZeRO-1 training step at overlap settings "
                         "{off, one bucket, per-group buckets} and emit "
                         "per-variant {ms_per_step, "
                         "exposed_collective_seconds, overlap by class} "
                         "rows in the JSON line — PC203 gates bucketed "
                         "exposed <= monolithic (runs INSTEAD of the "
                         "headline single-chip bench)")
    ap.add_argument("--comms", action="store_true",
                    help="run the interconnect sweep (telemetry.comms) "
                         "AFTER the timed loop on a small tp=2/pp=2 mesh "
                         "and embed per-axis fitted bandwidth + per-class "
                         "achieved_gbps in the headline JSON line "
                         "(verdict-gated via PC204; tools/comms_bench.py "
                         "is the standalone, full-control version)")
    args = ap.parse_args()

    if (args.schedule_sweep or args.overlap_sweep or args.comms) \
            and args.platform == "cpu":
        # the sweeps need a multi-device mesh; opportunistically request 8
        # virtual CPU devices — effective only when jax has not been
        # imported yet (the verify gate sets XLA_FLAGS in the environment,
        # which always works).  Merged against any user-provided XLA_FLAGS
        # with the user's flags WINNING on conflict — the old blind append
        # relied on XLA's silent duplicate-flag last-wins
        import os as _os

        from neuronx_distributed_training_tpu.optim.overlap import (
            merge_xla_flags,
        )

        merged, conflicts = merge_xla_flags(
            _os.environ.get("XLA_FLAGS", ""),
            ("--xla_force_host_platform_device_count=8",))
        for name, yours, dropped in conflicts:
            log(f"bench: XLA_FLAGS conflict on {name}: keeping your "
                f"{yours!r}, dropping {dropped!r}")
        _os.environ["XLA_FLAGS"] = merged

    dev = acquire_device(args.platform)

    if args.schedule_sweep:
        from neuronx_distributed_training_tpu.analysis import (
            perf_contract as _pc,
        )

        on_tpu_sweep = dev.platform == "tpu"
        steps, warmup = (args.steps, args.warmup) if on_tpu_sweep \
            else (min(args.steps, 4), min(args.warmup, 1))
        sweep = schedule_sweep(steps, warmup)
        payload = {
            "metric": "pipeline_schedule_sweep",
            "value": sweep.get("interleaved_over_1f1b") or 0.0,
            "unit": "interleaved_over_1f1b_step_time_ratio",
            # the planner prices interleaved at or below plain 1f1b —
            # a ratio <= 1.0 is the measured-wall-clock win
            "vs_baseline": sweep.get("interleaved_over_1f1b") or 0.0,
            "device": dev.device_kind,
            "seq_len": sweep.get("seq_len"),
            "num_layers": sweep.get("num_layers"),
            "pipeline_schedule": "sweep",
            "schedule_sweep": sweep,
            "note": ("all four pipeline schedules on one fixed mesh "
                     "(pp=2/nm=16/vp=2); per-row PC302 bubble calibration "
                     "and the PC303 interleaved<=1f1b ordering gate run in "
                     "tools/perf_contract.py --check"),
        }
        try:
            facts = _pc.perf_facts_from_bench(payload)
            key = args.contract_key or _pc.default_key(facts)
            payload["perf_contract"] = _pc.bench_verdict(key, facts)
            log(f"bench: perf contract [{key}]: "
                f"{payload['perf_contract']['verdict']}")
        except Exception as e:  # noqa: BLE001 — the verdict must not kill
            # the line, but its absence must be explained
            payload["perf_contract"] = {
                "verdict": "unavailable",
                "error": f"{type(e).__name__}: {e}"[:300],
            }
        emit(payload)
        return

    if args.overlap_sweep:
        from neuronx_distributed_training_tpu.analysis import (
            perf_contract as _pc,
        )

        on_tpu_ov = dev.platform == "tpu"
        steps, warmup = (args.steps, args.warmup) if on_tpu_ov \
            else (min(args.steps, 4), min(args.warmup, 1))
        sweep = overlap_sweep(steps, warmup)
        payload = {
            "metric": "zero1_overlap_sweep",
            "value": sweep.get("bucketed_over_off_exposed") or 0.0,
            "unit": "bucketed_over_off_exposed_collective_ratio",
            # bucketing + prefetch must EXPOSE less collective time than
            # the monolithic regather — a ratio <= 1.0 is the win
            "vs_baseline": sweep.get("bucketed_over_off_exposed") or 0.0,
            "device": dev.device_kind,
            "seq_len": sweep.get("seq_len"),
            "num_layers": sweep.get("num_layers"),
            "overlap_sweep": sweep,
            "note": ("the same dp-only ZeRO-1 step at overlap settings "
                     "{off, bucketed-1, bucketed-N}; PC203 gates bucketed "
                     "exposed <= monolithic and the committed baseline "
                     "ratchets per-variant drift in tools/perf_contract.py "
                     "--check"),
        }
        try:
            facts = _pc.perf_facts_from_bench(payload)
            key = args.contract_key or _pc.default_key(facts)
            payload["perf_contract"] = _pc.bench_verdict(key, facts)
            log(f"bench: perf contract [{key}]: "
                f"{payload['perf_contract']['verdict']}")
        except Exception as e:  # noqa: BLE001 — the verdict must not kill
            # the line, but its absence must be explained
            payload["perf_contract"] = {
                "verdict": "unavailable",
                "error": f"{type(e).__name__}: {e}"[:300],
            }
        emit(payload)
        return

    from neuronx_distributed_training_tpu.models import llama
    from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

    on_tpu = dev.platform == "tpu"
    if args.attn == "auto":
        attn_impl = "flash" if on_tpu else "core"
    else:
        attn_impl = args.attn
    # Flash attention handles seq 8192; naive core attention's O(s^2)
    # transients need the shorter default on small-HBM chips.
    seq = args.seq or ((8192 if attn_impl == "flash" else 4096) if on_tpu else 512)
    steps, warmup = (args.steps, args.warmup) if on_tpu else (
        min(args.steps, 4), min(args.warmup, 1))
    # the CPU smoke config is fixed-size (make_config ignores the budget)
    hbm = dev.memory_stats()["bytes_limit"] if on_tpu else 0

    # Regime definitions (reference precision matrix,
    # training_orchestrator.py:104-137):
    #  - mixed_precision: bf16 compute, fp32 master + opt state (+fp32 grad
    #    accum) -> ~18 resident bytes/param incl. transient fp32 grads
    #  - bf16SR: everything bf16 -> ~8 bytes/param incl. transient grads
    # The raw blocks are the single source both the measured policy AND the
    # plan-topk ModelFacts derive from (they must agree or the predicted-vs-
    # measured comparison silently compares different precisions).
    precision_blocks = {
        "mixed_precision": "mixed_precision",
        "bf16": {"type": "bf16SR", "optimizer_dtype": "bf16",
                 "grad_accum_dtype": "bf16"},
    }
    regime_bytes_per_param = {"mixed_precision": 18.0, "bf16": 8.0}
    regimes = {
        name: (DtypePolicy.from_precision_config(block),
               regime_bytes_per_param[name])
        for name, block in precision_blocks.items()
    }
    if args.regime == "mixed":
        wanted = ["mixed_precision"]
    elif args.regime == "bf16":
        wanted = ["bf16"]
    else:
        wanted = ["mixed_precision", "bf16"] if on_tpu else ["mixed_precision"]

    import dataclasses

    tied = not args.untied
    results: dict[str, dict] = {}
    used_cfgs: dict[str, object] = {}
    for name in wanted:
        policy, bpp = regimes[name]
        layers = args.layers or (layer_budget(hbm, bpp, tied=tied)
                                 if on_tpu else None)
        cfg = make_config(llama, on_tpu, attn_impl, seq, layers, hbm, bpp,
                          tied=tied, block_q=args.block_q, block_kv=args.block_kv)
        if args.remat != "selective":
            cfg = dataclasses.replace(
                cfg, activations_checkpoint_granularity=(
                    None if args.remat == "none" else args.remat))
        log(f"bench[{name}]: device={dev.device_kind} layers={cfg.num_layers} "
            f"seq={seq} mbs={args.mbs} attn={cfg.attention_impl} tied={tied}")
        # a regime that fails (out of memory included) raises: the run exits
        # non-zero instead of reporting the regimes that happened to fit
        results[name] = run_bench(
            dev, cfg, policy, seq, args.mbs, steps, warmup,
            trace=args.trace, tensorstats=args.tensorstats)
        results[name]["tied_embeddings"] = tied
        used_cfgs[name] = cfg

    # headline: prefer the baseline regime (mixed_precision), but a
    # single-layer stack never headlines over a multi-layer one — the
    # round-3 contract is a multi-layer, pinned-config number.  On a 16G
    # chip the mixed regime's fp32 master+opt state for the tied 0.53B-param
    # embedding alone (~9.5 GB) can cap it at 1 layer; the bf16 regime then
    # carries the multi-layer headline and mixed is reported alongside.
    def _pref(name: str) -> tuple:
        r = results[name]
        return (r["num_layers"] > 1, name == "mixed_precision",
                r["mfu"] or 0.0)

    def pct(mfu):
        return None if mfu is None else round(100 * mfu, 2)

    headline = max(results, key=_pref)
    r = results[headline]
    payload = {
        "metric": "llama3_8B_pretrain_mfu",
        "value": pct(r["mfu"]),
        "unit": "percent_mfu",
        "vs_baseline": None if r["mfu"] is None else round(r["mfu"] / 0.45, 4),
        "regime": headline,
        "tokens_per_sec_per_chip": r["tokens_per_sec"],
        "ms_per_step": r["ms_per_step"],
        "device": dev.device_kind,
        "attn_impl": attn_impl,
        "num_layers": r["num_layers"],
        "tied_embeddings": r.get("tied_embeddings", tied),
        "seq_len": seq,
        # the trainer's telemetry schema (metrics.jsonl / run_summary.json
        # key names): mfu as a FRACTION alongside the percent headline, plus
        # the headline regime's compile census
        "mfu": None if r["mfu"] is None else round(r["mfu"], 6),
        "compile_seconds": r.get("compile_seconds"),
        "collectives": r.get("collectives"),
        "memory_analysis": r.get("memory_analysis"),
        # measured memory (telemetry.memory; perf-contract PC501 gates the
        # peak, PC502 the predicted-vs-measured agreement when a planner
        # prediction rides along)
        "peak_hbm_bytes": r.get("peak_hbm_bytes"),
        "hbm_headroom_fraction": r.get("hbm_headroom_fraction"),
        "peak_hbm_source": r.get("peak_hbm_source"),
        # numerics health (telemetry.health): fast-but-diverging vs healthy
        "nonfinite_steps": r.get("nonfinite_steps"),
        "skipped_updates": r.get("skipped_updates"),
        "final_grad_norm": r.get("final_grad_norm"),
        # headline regime's static graph-audit verdict (analysis.graph_audit)
        "graph_audit": r.get("graph_audit"),
        # measured device-time overlap (--trace; None when not captured)
        "achieved_overlap": r.get("achieved_overlap"),
        "exposed_collective_seconds": r.get("exposed_collective_seconds"),
        # pipeline-schedule telemetry (run_summary.json key names): the
        # single-chip bench runs unpipelined, so the headline prediction is
        # 0.0 — the field exists so the bench trajectory and trainer
        # summaries share a schema (plan-topk rows carry per-plan values)
        "pipeline_schedule": "none",
        "bubble_fraction_predicted": 0.0,
        "note": ("deepest Llama-3-8B-shape stack fitting single-chip HBM "
                 "(tied embeddings, pinned config); MFU is per-layer-shape-bound"),
    }
    for name, res in results.items():
        payload[f"mfu_{name}"] = pct(res["mfu"])
        payload[f"layers_{name}"] = res["num_layers"]
        payload[f"graph_audit_{name}"] = res.get("graph_audit")
    if args.plan_topk and headline in used_cfgs:
        # measure the planner's top-N plans for the HEADLINE workload and
        # score the cost model's ranking against reality
        try:
            payload["plan_topk"] = plan_topk_measure(
                dev, used_cfgs[headline], regimes[headline][0],
                precision_blocks[headline], seq, args.mbs, steps, warmup,
                args.plan_topk,
            )
            log(f"bench: plan-topk kendall_tau="
                f"{payload['plan_topk']['kendall_tau']}")
        except Exception as e:  # noqa: BLE001 — the headline line must
            # survive a planner failure
            payload["plan_topk"] = {"error": f"{type(e).__name__}: {e}"[:500]}
            log(f"bench: plan-topk failed: {payload['plan_topk']['error']}")
    drill = load_last_drill()
    if drill.get("ok"):
        # elastic-resume drill trail (tools/elastic_drill.py): restart cost
        # and post-resume goodput from the last completed drill
        payload["restart_cost_seconds"] = drill.get("restart_cost_seconds")
        payload["goodput_fraction"] = drill.get("goodput_fraction")
        payload["drill"] = {
            k: drill.get(k)
            for k in ("date", "mode", "phase", "world", "resume_world",
                      "replanned", "max_loss_diff")
        }
        if drill.get("integrity"):
            # corruption-drill leg (elastic_drill --smoke): which injection
            # kind was survived and how far the walk-back went
            payload["drill"]["integrity"] = drill["integrity"]
    if args.comms:
        # interconnect sweep AFTER the timed loop (telemetry.comms): time
        # the collective classes on a small tp=2/pp=2 mesh, fit per-axis
        # bandwidth/latency, and embed the facts block — PC204 then rides
        # the same verdict the headline carries
        try:
            import jax as _jax

            from neuronx_distributed_training_tpu.autotune.topology import (
                resolve_topology,
            )
            from neuronx_distributed_training_tpu.parallel.mesh import (
                MeshConfig,
                build_mesh,
            )
            from neuronx_distributed_training_tpu.telemetry import (
                comms as _comms,
            )

            devs = _jax.devices()
            tp = 2 if len(devs) % 2 == 0 and len(devs) >= 2 else 1
            pp = 2 if len(devs) % (tp * 2) == 0 and len(devs) >= 4 else 1
            mesh = build_mesh(MeshConfig(tensor_model_parallel_size=tp,
                                         pipeline_model_parallel_size=pp),
                              devs)
            sizes = (1 << 18, 1 << 20) if not on_tpu else (1 << 22, 1 << 24)
            axis_results = _comms.run_comms_sweep(
                mesh, sizes_bytes=sizes, warmup=1, reps=3)
            topo = resolve_topology(device=devs[0])
            summary = _comms.build_comms_summary(
                axis_results, topology_name=topo.name,
                prior_bandwidth_bytes=topo.ici_bandwidth_bytes,
                prior_latency_seconds=topo.ici_latency_seconds,
                device_skew=_comms.measure_device_skew(devs))
            payload["comms"] = _comms.bench_comms_facts(summary)
            payload["comms_findings"] = summary.get("findings") or []
            log(f"bench: comms sweep fitted axes="
                f"{sorted((payload['comms'].get('axes') or {}))}")
        except Exception as e:  # noqa: BLE001 — the headline must survive
            payload["comms"] = None
            payload["comms_error"] = f"{type(e).__name__}: {e}"[:300]
            log(f"bench: comms sweep failed: {payload['comms_error']}")
    # the perf-contract verdict: the measured line checked against the
    # committed per-topology baseline (analysis.perf_contract) — emit()
    # REFUSES a headline line without this field, and "no_baseline" is an
    # honest verdict where silence would not be
    try:
        from neuronx_distributed_training_tpu.analysis import (
            perf_contract as _pc,
        )

        facts = _pc.perf_facts_from_bench(payload)
        key = args.contract_key or _pc.default_key(facts)
        payload["perf_contract"] = _pc.bench_verdict(key, facts)
        log(f"bench: perf contract [{key}]: "
            f"{payload['perf_contract']['verdict']}")
    except Exception as e:  # noqa: BLE001 — the verdict must not kill the
        # line, but its absence must be explained
        payload["perf_contract"] = {
            "verdict": "unavailable",
            "error": f"{type(e).__name__}: {e}"[:300],
        }
    emit(payload)


if __name__ == "__main__":
    main()
