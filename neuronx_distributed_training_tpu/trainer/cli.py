#!/usr/bin/env python
"""Training CLI — the L0 launch layer.

Replaces the reference's ``train.sh`` + ``training_orchestrator.py`` (torchrun +
Hydra + env-var projection, reference ``examples/train.sh:1-29``,
``training_orchestrator.py:25-149``) with one entry point:

    python examples/train.py --config examples/conf/hf_llama3_8B_config.yaml \
        [--set trainer.max_steps=100] [--compile-only]

- ``--set a.b.c=v`` dotted overrides (the Hydra override surface);
- ``--compile-only`` lowers + compiles the train step and exits — the
  ``COMPILE=1`` / ``neuron_parallel_compile`` AOT-warmup analogue
  (``train.sh:19-22``), populating the persistent XLA compilation cache;
- ``TRAIN_ITERS`` env var overrides ``trainer.max_steps`` (the reference's
  test hook, ``training_orchestrator.py:48-58``);
- multi-host: call ``jax.distributed.initialize()`` automatically when the
  cluster env provides coordination (TPU pods auto-detect).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time

logger = logging.getLogger("nxdt.train")


def parse_overrides(pairs: list[str]) -> dict:
    out = {}
    for p in pairs:
        if "=" not in p:
            raise SystemExit(f"override must be key.path=value, got {p!r}")
        k, _, v = p.partition("=")
        try:
            import yaml

            out[k] = yaml.safe_load(v)
        except Exception:
            out[k] = v
    return out


def maybe_init_distributed(jax) -> bool:
    """Multi-host rendezvous from the cluster environment.

    The reference's ``train_setup.sh`` cases (SLURM nodelist -> MASTER_ADDR,
    MPI-on-EKS ``OMPI_COMM_WORLD_RANK``, reference ``train_setup.sh:8-67``)
    are handled by ``utils.launch.detect_cluster`` — an explicit
    ``(coordinator, num_processes, process_id)`` triple.  TPU-pod metadata
    (``COORDINATOR_ADDRESS``/``MEGASCALE_*``) keeps jax's own no-arg
    auto-detection, which owns that handshake.
    """
    env = os.environ
    from neuronx_distributed_training_tpu.utils.launch import (
        detect_cluster,
        initialize_distributed,
    )

    spec = detect_cluster(env)
    if spec.is_multiprocess:
        initialize_distributed(spec)
        return True
    explicit_env = bool(env.get("COORDINATOR_ADDRESS")
                        or env.get("MEGASCALE_COORDINATOR_ADDRESS"))
    if explicit_env:
        jax.distributed.initialize()  # jax's built-in cluster auto-detection
        logger.info(
            "distributed: process %d/%d", jax.process_index(), jax.process_count()
        )
        return True
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True, help="YAML config (reference schema)")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="KEY=VAL", help="dotted config override")
    ap.add_argument("--compile-only", action="store_true",
                    help="AOT-compile the train step and exit (COMPILE=1 analogue)")
    ap.add_argument("--audit-only", action="store_true",
                    help="pre-flight static audit (analysis.graph_audit) of "
                         "THIS config at true size on this machine's "
                         "devices, then exit non-zero on error findings — "
                         "no params materialized, no data opened")
    ap.add_argument("--autotune", nargs="?", const=0, type=int, default=None,
                    metavar="TOP_K",
                    help="plan the launch config before materializing "
                         "(autotune planner, docs/autotuning.md): rank the "
                         "legal tp/pp/cp/ep/mbs/remat/schedule lattice for "
                         "THIS machine's chip count, audit the top "
                         "candidates, impose the winner on the config, and "
                         "record the plan in run_summary.json.  Optional "
                         "value overrides autotune.top_k")
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="START[:NUM]",
                    help="windowed device-time capture "
                         "(exp_manager.telemetry.trace): trace NUM steps "
                         "from START (default 1:3), analyze achieved "
                         "compute/comms overlap, and write "
                         "trace_summary.json next to run_summary.json — "
                         "shorthand for the --set knobs "
                         "(docs/observability.md 'Device-time profiling')")
    ap.add_argument("--compilation-cache", default=None,
                    help="persistent XLA compilation cache dir, overriding "
                         "JAX_COMPILATION_CACHE_DIR and the in-checkout "
                         "default (utils/compile_cache.py)")
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="force a JAX platform before backend init (cpu for "
                         "off-hardware smoke runs)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    # the module's imports that cost (the loop's bring the models, the
    # optimizer and the data modules; orbax waits for the first
    # ``Checkpointer``) and the backend's first use are phases of the
    # start-up timeline (docs/observability.md "Start-up timeline"), each
    # bracketed where it stands; the few milliseconds of the later ones lie
    # between phases
    from neuronx_distributed_training_tpu.telemetry.spans import (
        startup_add,
        startup_phase,
    )

    with startup_phase("startup/imports"):
        from neuronx_distributed_training_tpu.utils.compile_cache import (
            configure_compilation_cache,
        )

    configure_compilation_cache(args.compilation_cache)

    with startup_phase("startup/backend"):
        maybe_init_distributed(jax)

    with startup_phase("startup/imports"):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

    overrides = parse_overrides(args.overrides)
    if os.environ.get("TRAIN_ITERS"):  # reference test hook
        overrides["trainer.max_steps"] = int(os.environ["TRAIN_ITERS"])
    if args.trace is not None:
        overrides["exp_manager.telemetry.trace.enabled"] = True
        if args.trace:
            start, _, num = args.trace.partition(":")
            try:
                overrides["exp_manager.telemetry.trace.start_step"] = int(start)
                if num:
                    overrides["exp_manager.telemetry.trace.num_steps"] = int(num)
            except ValueError:
                raise SystemExit(
                    f"--trace wants START[:NUM] step numbers, got {args.trace!r}")

    if args.audit_only:
        from neuronx_distributed_training_tpu.analysis.graph_audit import (
            audit_config,
        )

        report = audit_config(args.config, shrink=False, overrides=overrides)
        print(report.format())
        raise SystemExit(1 if report.failed("error") else 0)

    cfg = load_config(args.config, overrides)

    # -- engineered overlap env (distributed_strategy.overlap.xla_lhs): the
    # latency-hiding-scheduler flag set merges into XLA_FLAGS BEFORE the
    # backend initializes (first jax.devices() call below).  User-provided
    # flags win; each dropped knob flag is warned, not silently last-wins.
    from neuronx_distributed_training_tpu.optim.overlap import (
        OverlapConfig,
        merge_xla_flags,
        xla_lhs_flags,
    )

    overlap_cfg = OverlapConfig.from_config(
        (cfg.get("distributed_strategy", {}) or {}).get("overlap"))
    if overlap_cfg.xla_lhs:
        platform = args.platform or os.environ.get("JAX_PLATFORMS") or "tpu"
        lhs = xla_lhs_flags(platform)
        if not lhs:
            logging.getLogger(__name__).warning(
                "overlap.xla_lhs: no latency-hiding flag set for platform "
                "%r — knob is a no-op (TPU only)", platform)
        else:
            merged, conflicts = merge_xla_flags(
                os.environ.get("XLA_FLAGS", ""), lhs)
            for name, keep, drop in conflicts:
                logging.getLogger(__name__).warning(
                    "overlap.xla_lhs: XLA_FLAGS already sets %s (%s); "
                    "keeping yours, dropping knob flag %s", name, keep, drop)
            os.environ["XLA_FLAGS"] = merged

    # -- elastic replan-on-resume (docs/elasticity.md): if a resumable
    # checkpoint's manifest names a different world size than the live fleet,
    # re-run the autotune planner on the NEW world size (filtered to
    # checkpoint-layout-compatible plans) and impose the winner BEFORE
    # anything materializes.  Runs before --autotune: a replan IS the plan
    # for this incarnation.
    replan = None
    from neuronx_distributed_training_tpu.trainer.control import (
        EXIT_ALL_CORRUPT,
        EXIT_DATA_STALL,
        EXIT_ELASTIC_REFUSED,
        exit_code_for_stop,
    )
    from neuronx_distributed_training_tpu.trainer.elastic import (
        ElasticConfig,
        ElasticResumeError,
        maybe_replan,
    )

    elastic_cfg = ElasticConfig.from_config(
        dict(cfg.get("exp_manager", {}) or {}).get("elastic"))
    if elastic_cfg.enabled:
        from neuronx_distributed_training_tpu.checkpoint import (
            CheckpointIntegrityError,
        )

        try:
            with startup_phase("startup/backend"):
                n_devices = len(jax.devices())
            t_replan = time.perf_counter()
            replan = maybe_replan(cfg, n_devices, elastic=elastic_cfg)
        except ElasticResumeError as e:
            # curated operator-facing refusal (the message carries the --set
            # remediation) — a clean one-line exit with the tagged code
            # (trainer.control exit-code table), not a traceback
            print(f"elastic resume refused: {e}", file=sys.stderr)
            raise SystemExit(EXIT_ELASTIC_REFUSED) from e
        except CheckpointIntegrityError as e:
            # every retained checkpoint failed verification at discovery —
            # the message names each step's verdict (docs/elasticity.md
            # "Integrity & walk-back"); the tagged code tells the
            # orchestrator to PAGE, not blind-restart
            print(f"elastic resume refused: {e}", file=sys.stderr)
            raise SystemExit(EXIT_ALL_CORRUPT) from e
        if replan.replanned:
            # on the start-up timeline under the name fit() accounts it by
            startup_add("replan", t_replan)
            cfg = replan.cfg
            logger.warning(
                "elastic replan imposed for %d chips (was %d): see "
                "run_summary.json elastic section",
                replan.record["new_world"], replan.record["old_world"],
            )

    # -- autotune: plan BEFORE materializing (no params, no data yet) ------
    plan_report = None
    at_block = dict(cfg.get("autotune", {}) or {})
    if replan is not None and replan.replanned:
        # the replanner already planned this world size against the
        # checkpoint's layout constraints; a second, layout-blind autotune
        # pass could impose an un-resumable mesh on top of it
        if args.autotune is not None or at_block.get("enabled"):
            logger.info("autotune skipped: elastic replan already planned "
                        "this restart")
    elif args.autotune is not None or at_block.get("enabled"):
        from neuronx_distributed_training_tpu.autotune import plan_config

        top_k = (args.autotune if (args.autotune or 0) > 0
                 else int(at_block.get("top_k", 5)))
        with startup_phase("startup/backend"):
            chips = len(jax.devices())
        plan_report = plan_config(
            cfg, chips=chips,
            topology=at_block.get("topology"),
            top_k=top_k,
            hbm_headroom=float(at_block.get("hbm_headroom", 0.9)),
            max_mbs=int(at_block.get("max_micro_batch_size", 8)),
            max_devices=min(8, chips),
        )
        print(plan_report.format())
        winner = plan_report.winner
        if winner is None:
            raise SystemExit(
                f"autotune: no surviving plan for {chips} chips"
                + (f" ({plan_report.error})" if plan_report.error else "")
            )
        if replan is not None and replan.manifest is not None:
            # a resumable checkpoint binds this launch even at the SAME
            # world size: a layout-blind winner could impose an
            # un-resumable mesh — take the best layout-compatible candidate
            from neuronx_distributed_training_tpu.trainer.elastic import (
                plan_layout_reason,
            )

            compatible = next(
                (c for c in plan_report.candidates
                 if not c.discarded
                 and plan_layout_reason(replan.manifest, c.plan) is None),
                None)
            if compatible is None:
                raise SystemExit(
                    "autotune: no candidate keeps the resumable "
                    "checkpoint's layer layout — drop --autotune to resume "
                    "with the declared mesh, or start fresh with "
                    "exp_manager.resume_if_exists=false")
            if compatible is not winner:
                logger.warning(
                    "autotune: top plan is incompatible with the resumable "
                    "checkpoint's layer layout; imposing %s instead",
                    compatible.plan.describe())
            winner = compatible
        logger.info("autotune: imposing %s", winner.plan.describe())
        cfg = load_config(
            args.config,
            {**overrides, **winner.plan.overrides(plan_report.facts)},
        )

    trainer = Trainer.from_config(cfg, enable_checkpointing=not args.compile_only)
    if replan is not None and replan.replanned:
        # fit() accounts the replan wall time as a goodput span and persists
        # the old-plan -> new-plan record in run_summary.json's elastic
        # section at teardown
        trainer.replan_record = replan.record
    if replan is not None and replan.integrity_trail:
        # discovery already verified (and possibly quarantined/walked back):
        # carry that trail so run_summary.json's integrity section reflects
        # the WHOLE restore story, not just the trainer's own (already
        # cleaned) restore
        trainer.discovery_integrity_trail = replan.integrity_trail
    if plan_report is not None:
        # the chosen plan becomes a static run fact: the compile census
        # carries it, and run_summary.json gets the full ranked report
        trainer.run_facts["autotune_plan"] = winner.plan.describe()
        trainer.exp.write_run_summary({"autotune": plan_report.to_dict()})

    if args.compile_only:
        from neuronx_distributed_training_tpu.parallel import sharding as shd

        batch = next(trainer.data_module.sharded_batches(trainer.mesh))
        # compile inside the same mesh context fit() uses, so the cached
        # executable is byte-identical to the real training step
        with trainer.mesh, shd.use_mesh(trainer.mesh):
            lowered = trainer.train_step.lower(
                trainer.params, trainer.opt_state, batch, jax.random.PRNGKey(0)
            )
            compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        logger.info("compile-only: train step compiled; flops=%s bytes=%s",
                    cost.get("flops"), cost.get("bytes accessed"))
        return

    from neuronx_distributed_training_tpu.data import DataStallError

    try:
        metrics = trainer.fit()
    except DataStallError as e:
        # the data-stall watchdog already dumped its bundle; exit with the
        # tagged code so the orchestrator pages instead of blind-restarting
        # into the same dead mount
        print(f"data stall: {e}", file=sys.stderr)
        raise SystemExit(EXIT_DATA_STALL) from e
    logger.info("done: %s", {k: round(v, 4) for k, v in metrics.items()})
    # failure-class exit codes (trainer.control, docs/observability.md
    # "Fleet control"): a health/alert halt exits tagged so restart-vs-page
    # policy needs nothing but the code; graceful stops (preemption,
    # operator stop, max_time) exit 0 — resume_if_exists continues the run
    code = exit_code_for_stop(getattr(trainer, "stop_class", None))
    if code:
        logger.warning("exiting with tagged code %d (%s)", code,
                       trainer.stop_class)
        raise SystemExit(code)


if __name__ == "__main__":
    main()
