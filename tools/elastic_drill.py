#!/usr/bin/env python
"""Preemption drill harness: kill (or gracefully preempt) a tiny-llama run at
a configurable point, resume it — optionally on a DIFFERENT device count, so
the restart-time autotune replanner has to re-mesh — and prove the resumed
loss trajectory matches an uninterrupted control run at pinned tolerance.

This is the fleet-survivability acceptance gate for the elastic resume path
(docs/elasticity.md): a health-halt or SIGTERM must leave the run one
auto-resume away from continuing, whatever the post-shrink fleet looks like.

    python tools/elastic_drill.py --smoke             # CI gate: dp 4 -> 2 kill drill
    python tools/elastic_drill.py --at-step 3 --phase save --mode sigterm \
        --world 4 --resume-world 8 --json -

The drill runs single-process on the virtual CPU mesh (the same 8-device
harness the test suite uses): "world size" is a device-subset choice, the
kill is :class:`~neuronx_distributed_training_tpu.trainer.elastic.
SimulatedPreemption` raised at the injection point — everything downstream of
the signal (drain, manifest, replan, resharded restore, goodput accounting)
is the REAL production path.  ``tests/test_elastic.py`` drives the same
:func:`run_drill` entry, so the CLI and the regression suite cannot drift.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from pathlib import Path
from typing import Any, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # tools/_jsonout

logger = logging.getLogger("nxdt.elastic_drill")

#: loss-trajectory pin for cross-dp resumes: the resumed run re-reduces the
#: same global batches over a different dp grouping, so per-step losses agree
#: to reduction-order noise, not bitwise (same-dp resumes ARE bitwise and the
#: harness asserts exact equality there)
DEFAULT_LOSS_TOL = 2e-3


def tiny_llama_config(workdir: str | Path, *, name: str = "drill",
                      max_steps: int = 6, save_every: int = 2,
                      seed: int = 7) -> dict[str, Any]:
    """The drill's tiny-llama raw config mapping: synthetic deterministic
    data (content is a pure function of row index — identical batches at any
    dp), per-step logging, goodput telemetry on, elastic resume on."""
    return {
        "name": name,
        "model_source": "hf",
        "seed": seed,
        "trainer": {"max_steps": max_steps, "log_every_n_steps": 1},
        "exp_manager": {
            "exp_dir": str(workdir),
            "resume_if_exists": True,
            "checkpoint_callback_params": {
                "save_top_k": 2, "every_n_train_steps": save_every,
                "async_checkpointing": True,
            },
            "elastic": {"enabled": True, "grace_period_seconds": 10.0},
            "telemetry": {"spans": True, "goodput": True,
                          "compile_census": False, "mfu": False},
        },
        "distributed_strategy": {"tensor_model_parallel_size": 1,
                                 "zero1": True},
        "data": {"global_batch_size": 8, "micro_batch_size": 1,
                 "seq_length": 32, "synthetic": True},
        "model": {
            "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
            "num_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "max_position_embeddings": 32,
            "optim": {"name": "adamw_fp32OptState", "lr": 1e-3,
                      "sched": {"name": "LinearAnnealingWithWarmUp",
                                "warmup_steps": 2, "max_steps": max_steps}},
        },
        "precision": {"type": "mixed_precision"},
    }


def read_losses(run_dir: str | Path) -> dict[int, float]:
    """``{step: loss}`` from a run dir's ``metrics.jsonl`` — last record per
    step wins (a resumed run re-logs the steps it re-trains)."""
    out: dict[int, float] = {}
    path = Path(run_dir) / "metrics.jsonl"
    if not path.exists():
        return out
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn tail line from a killed run
        if isinstance(rec.get("step"), int) and "loss" in rec:
            out[rec["step"]] = float(rec["loss"])
    return out


def _run_dir(cfg: Any) -> Path:
    from neuronx_distributed_training_tpu.trainer.exp_manager import (
        experiment_base_dir,
        latest_version,
    )

    base = experiment_base_dir(dict(cfg))
    v = latest_version(base)
    return base / f"version_{v if v is not None else 0}"


def run_segment(raw_cfg: dict, devices: list, *,
                fault: Optional[Any] = None,
                replan_world: Optional[int] = None,
                peer_words: Optional[Any] = None) -> dict[str, Any]:
    """One trainer incarnation of the drill: build (optionally after a
    restart-time replan for ``replan_world`` chips), attach the fault
    injector, run ``fit()``, and report what happened.

    Returns ``{"killed": bool, "metrics": dict|None, "trainer": Trainer,
    "run_dir": Path, "replanned": bool, "record": dict|None}`` — ``killed``
    is True when the injected :class:`SimulatedPreemption` fired (the
    simulated SIGKILL: fit() died, teardown still drained the async save)."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.elastic import (
        SimulatedPreemption,
        maybe_replan,
    )
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(raw_cfg)
    record, itrail = None, None
    if replan_world is not None:
        result = maybe_replan(cfg, int(replan_world))
        cfg, record, itrail = result.cfg, result.record, result.integrity_trail
    trainer = Trainer.from_config(cfg, devices=list(devices))
    if record is not None:
        trainer.replan_record = record
    if itrail is not None:
        trainer.discovery_integrity_trail = itrail
    if fault is not None:
        trainer.fault_injector = fault
    if peer_words is not None:
        # the control plane's simulated-peer seam: extra control-word bits
        # standing in for other hosts' contributions on this single-process
        # mesh (trainer.control, docs/observability.md "Fleet control")
        trainer.control_peer_words = peer_words
    killed, metrics = False, None
    try:
        metrics = trainer.fit()
    except SimulatedPreemption as e:
        killed = True
        logger.info("drill: %s", e)
    return {"killed": killed, "metrics": metrics, "trainer": trainer,
            "run_dir": _run_dir(cfg), "replanned": record is not None,
            "record": record}


def _tree_max_diff(a: Any, b: Any) -> float:
    import jax
    import numpy as np

    diffs = jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(
            np.asarray(x, dtype=np.float64) - np.asarray(y, np.float64))))
        if np.asarray(x).size else 0.0,
        a, b,
    )
    return max(jax.tree_util.tree_leaves(diffs), default=0.0)


def run_drill(workdir: str | Path, *, at_step: int = 3, phase: str = "step",
              mode: str = "kill", world: int = 4,
              resume_world: Optional[int] = 2, total_steps: int = 6,
              save_every: int = 2,
              loss_tol: float = DEFAULT_LOSS_TOL) -> dict[str, Any]:
    """The full drill: control run, injected fault, resume (replanned when
    the world changed), trajectory + state comparison.  Raises
    ``AssertionError`` with a diagnostic on any continuity violation.

    Returns the drill report (the CLI's JSON payload)."""
    import jax

    from neuronx_distributed_training_tpu.trainer.elastic import FaultInjector

    devices = jax.devices()
    resume_world = int(resume_world if resume_world is not None else world)
    if max(world, resume_world) > len(devices):
        raise ValueError(
            f"drill wants {max(world, resume_world)} devices, "
            f"have {len(devices)}")
    workdir = Path(workdir)

    # 1. control: uninterrupted run at the original world size
    control = run_segment(
        tiny_llama_config(workdir / "control", max_steps=total_steps,
                          save_every=save_every),
        devices[:world])
    assert control.get("metrics"), "control run produced no metrics"

    # 2. the doomed run: same config, fault injected.  A restore-phase fault
    # belongs to the RESUME incarnation (a fresh start never restores), so
    # for phase="restore" the doomed run is interrupted by a plain step kill
    # — its job is only to leave an interrupted run + checkpoint behind.
    drill_cfg = tiny_llama_config(workdir / "drill", max_steps=total_steps,
                                  save_every=save_every)
    doomed_fault = (FaultInjector(at_step=at_step, mode="kill", phase="step")
                    if phase == "restore"
                    else FaultInjector(at_step=at_step, mode=mode, phase=phase))
    doomed = run_segment(drill_cfg, devices[:world], fault=doomed_fault)
    if mode == "kill" or phase == "restore":
        assert doomed["killed"], (
            f"FaultInjector({doomed_fault.mode}, {doomed_fault.phase}, "
            f"step {at_step}) never fired — the drill tested nothing")
    else:
        # sigterm mode completes fit() normally, so "killed" proves nothing:
        # the injector's own fired flag is the evidence the grace-window
        # path was exercised (e.g. an at_step past the last boundary would
        # otherwise produce a clean run and a misleading downstream failure)
        assert doomed_fault.fired, (
            f"FaultInjector(sigterm, {phase}, step {at_step}) never fired — "
            f"the drill tested nothing (at_step past the last boundary?)")
    # the drain-on-teardown contract: whatever save was in flight when the
    # fault hit must have committed — a resumable checkpoint exists
    from neuronx_distributed_training_tpu.trainer.elastic import (
        discover_checkpoint_dir,
        read_latest_manifest,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config

    ck_dir = discover_checkpoint_dir(load_config(drill_cfg))
    assert ck_dir is not None, "no checkpoint survived the injected fault"
    manifest = read_latest_manifest(ck_dir)
    assert manifest is not None, (
        f"checkpoint under {ck_dir} has no topology manifest — "
        f"world-size-agnostic resume is broken")
    assert int(manifest["world_size"]) == world, manifest

    # 3. resume — on the (possibly different) world; replan when it changed.
    # phase="restore": the fault rides the FIRST resume incarnation (kill
    # dies mid-restore, sigterm is a notice landing mid-restore) and a
    # second, clean resume proves the save survived and the run continues.
    replan_world = resume_world if resume_world != world else None
    replanned, record = False, None
    if phase == "restore":
        # at_step=0: fire on the first restore, whatever step it resumes
        restore_fault = FaultInjector(at_step=0, mode=mode, phase="restore")
        faulted = run_segment(
            drill_cfg, devices[:resume_world], fault=restore_fault,
            replan_world=replan_world)
        replanned, record = faulted["replanned"], faulted["record"]
        assert restore_fault.fired, (
            "FaultInjector(restore) never fired on the resume incarnation — "
            "the drill tested nothing")
        if mode == "kill":
            assert faulted["killed"], (
                f"FaultInjector(kill, restore, step 0) never fired on the "
                f"resume incarnation — the drill tested nothing")
            # a kill mid-restore (checkpoint read, nothing applied) must
            # leave the save untouched and still resumable
            m2 = read_latest_manifest(ck_dir)
            assert m2 is not None and int(m2["step"]) == int(
                manifest["step"]), (
                f"mid-restore kill corrupted the checkpoint: manifest "
                f"{manifest.get('step')} -> {m2 and m2.get('step')}")
        else:
            assert faulted.get("metrics") is not None, (
                "sigterm-mode restore drill produced no metrics")
    resumed = run_segment(drill_cfg, devices[:resume_world],
                          replan_world=replan_world)
    assert resumed.get("metrics"), "resumed run produced no metrics"
    replanned = replanned or resumed["replanned"]
    record = resumed["record"] or record
    if resume_world != world:
        assert replanned, (
            f"world changed {world} -> {resume_world} but no replan happened")

    # 4. loss-trajectory continuity: every step the resumed run trained must
    # match the control at pinned tolerance (identical synthetic batches,
    # different dp reduction grouping)
    control_losses = read_losses(control["run_dir"])
    drill_losses = read_losses(resumed["run_dir"])
    common = sorted(set(control_losses) & set(drill_losses))
    assert common and max(common) == total_steps, (
        f"resumed run never reached step {total_steps}: "
        f"control={sorted(control_losses)}, drill={sorted(drill_losses)}")
    worst = max(abs(control_losses[s] - drill_losses[s]) for s in common)
    assert worst <= loss_tol, (
        f"loss trajectory diverged after resume: max |Δloss|={worst:.3e} "
        f"> {loss_tol:.0e} over steps {common}")

    # 5. state equivalence at the horizon: bitwise at the same world size,
    # pinned tolerance across a reshard
    params_diff = _tree_max_diff(control["trainer"].params,
                                 resumed["trainer"].params)
    if resume_world == world and not replanned:
        assert params_diff == 0.0, (
            f"same-world resume must be bitwise: max param diff {params_diff:.3e}")
    else:
        assert params_diff <= loss_tol, (
            f"cross-world resume params diverged: max diff {params_diff:.3e}")

    # 6. the restart cost is accounted: run_summary.json carries the elastic
    # trail + goodput breakdown for the resumed incarnation
    summary = {}
    summary_path = Path(resumed["run_dir"]) / "run_summary.json"
    if summary_path.exists():
        summary = json.loads(summary_path.read_text())
    elastic_sec = dict(summary.get("elastic") or {})
    goodput = dict(summary.get("goodput") or {})
    assert elastic_sec.get("resumed"), (
        f"run_summary.json has no elastic resume trail: {summary_path}")
    restart_cost = (float(elastic_sec.get("restart_seconds", 0.0))
                    + float(elastic_sec.get("replan_seconds", 0.0)))
    import time

    report = {
        "ok": True,
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "at_step": at_step, "phase": phase, "mode": mode,
        "world": world, "resume_world": resume_world,
        "total_steps": total_steps,
        "resume_step": int(manifest.get("step", -1)),
        "replanned": replanned,
        "old_plan": (record or {}).get("old_plan"),
        "new_plan": (record or {}).get("new_plan"),
        "max_loss_diff": worst,
        "max_param_diff": params_diff,
        "loss_tol": loss_tol,
        "restart_cost_seconds": round(restart_cost, 3),
        "goodput_fraction": goodput.get("goodput_fraction"),
        "run_dir": str(resumed["run_dir"]),
    }
    return report


def run_corruption_drill(workdir: str | Path, *, kind: str = "byte_flip",
                         world: int = 4, resume_world: Optional[int] = 2,
                         total_steps: int = 6, save_every: int = 2,
                         loss_tol: float = DEFAULT_LOSS_TOL) -> dict[str, Any]:
    """The corruption drill (docs/elasticity.md "Integrity & walk-back"):
    complete a run, deliberately corrupt its NEWEST checkpoint with ``kind``
    (byte-flip / truncate / delete-item / stale-sidecar), then auto-resume —
    on a different world size when ``resume_world`` differs, so the replan
    path is exercised too — and prove, with no human intervention:

    - the corrupt step is detected, quarantined (renamed + ledger entry),
      and walked past;
    - the restored step is the newest GOOD one, and the elastic replan keys
      off the RESTORED step's manifest, not the corrupt latest;
    - the resumed loss trajectory matches the control at pinned tolerance;
    - the ``integrity`` trail lands in ``run_summary.json``.
    """
    import jax

    from neuronx_distributed_training_tpu.checkpoint import (
        inject_corruption,
    )
    from neuronx_distributed_training_tpu.checkpoint.integrity import (
        parse_quarantine_name,
        read_ledger,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.elastic import (
        discover_checkpoint_dir,
    )

    devices = jax.devices()
    resume_world = int(resume_world if resume_world is not None else world)
    if max(world, resume_world) > len(devices):
        raise ValueError(
            f"drill wants {max(world, resume_world)} devices, "
            f"have {len(devices)}")
    workdir = Path(workdir)

    # 1. control: uninterrupted run at the original world size
    control = run_segment(
        tiny_llama_config(workdir / "control", max_steps=total_steps,
                          save_every=save_every),
        devices[:world])
    assert control.get("metrics"), "control run produced no metrics"

    # 2. the victim: a CLEAN completed run — the corruption hits the store
    # after commit (bitrot / truncated upload), not the process
    drill_cfg = tiny_llama_config(workdir / "drill", max_steps=total_steps,
                                  save_every=save_every)
    victim = run_segment(drill_cfg, devices[:world])
    assert victim.get("metrics"), "victim run produced no metrics"
    ck_dir = discover_checkpoint_dir(load_config(drill_cfg))
    assert ck_dir is not None, "victim run left no checkpoint"
    steps = sorted(int(p.name) for p in ck_dir.iterdir() if p.name.isdigit())
    assert len(steps) >= 2, (
        f"corruption drill needs >= 2 retained steps to walk back over, "
        f"got {steps}")
    corrupted_step, expect_step = steps[-1], steps[-2]
    what = inject_corruption(ck_dir, corrupted_step, kind)
    logger.info("corruption drill: %s", what)

    # 3. auto-resume on the (possibly different) world — discovery must
    # verify, quarantine the corrupt newest, and key the replan off the
    # step actually restored
    replan_world = resume_world if resume_world != world else None
    resumed = run_segment(drill_cfg, devices[:resume_world],
                          replan_world=replan_world)
    assert resumed.get("metrics"), "resumed run produced no metrics"
    record = resumed["record"]
    if resume_world != world:
        assert resumed["replanned"], (
            f"world changed {world} -> {resume_world} but no replan happened")
        assert int(record["checkpoint_step"]) == expect_step, (
            f"replan keyed off step {record['checkpoint_step']}, not the "
            f"verified step {expect_step} — the replanned layout would chase "
            f"the corrupt latest")

    # 4. quarantine really happened: renamed dir + ledger entry, and the
    # corrupt step is invisible to discovery
    qnames = [p.name for p in ck_dir.iterdir()
              if parse_quarantine_name(p.name) == corrupted_step]
    assert qnames, (
        f"corrupt step {corrupted_step} was not quarantined "
        f"(dir contents: {sorted(p.name for p in ck_dir.iterdir())})")
    ledger_steps = [e.get("step") for e in read_ledger(ck_dir)]
    assert corrupted_step in ledger_steps, (
        f"quarantine ledger has no entry for step {corrupted_step}: "
        f"{ledger_steps}")
    # NOTE a fresh, healthy `<corrupted_step>` dir legitimately reappears:
    # the resumed run retrains through that step and saves it again — the
    # quarantined corpse and the new save coexist

    # 5. the integrity trail is in run_summary.json and names the facts
    summary_path = Path(resumed["run_dir"]) / "run_summary.json"
    summary = (json.loads(summary_path.read_text())
               if summary_path.exists() else {})
    trail = dict(summary.get("integrity") or {})
    assert int(trail.get("verified_step", -1)) == expect_step, trail
    assert int(trail.get("walk_back_count", 0)) >= 1, trail
    assert corrupted_step in (trail.get("quarantined_steps") or []), trail

    # 6. loss-trajectory continuity: the steps retrained after the walk-back
    # must match the control at pinned tolerance
    control_losses = read_losses(control["run_dir"])
    drill_losses = read_losses(resumed["run_dir"])
    common = sorted(set(control_losses) & set(drill_losses))
    assert common and max(common) == total_steps, (
        f"resumed run never reached step {total_steps}: "
        f"control={sorted(control_losses)}, drill={sorted(drill_losses)}")
    worst = max(abs(control_losses[s] - drill_losses[s]) for s in common)
    # same-world walk-back retrains from a bitwise-identical state over
    # identical synthetic batches -> bitwise; cross-dp re-reduces -> pinned
    tol = 0.0 if resume_world == world else loss_tol
    assert worst <= tol, (
        f"loss trajectory diverged after corruption walk-back: "
        f"max |Δloss|={worst:.3e} > {tol:.0e} over steps {common}")

    import time

    return {
        "ok": True,
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "kind": kind,
        "what": what,
        "world": world, "resume_world": resume_world,
        "corrupted_step": corrupted_step,
        "resume_step": expect_step,
        "walked_back": int(trail.get("walk_back_count", 0)),
        "quarantined": trail.get("quarantined_steps"),
        "replanned": bool(resumed["replanned"]),
        "max_loss_diff": worst,
        "loss_tol": loss_tol,
        "run_dir": str(resumed["run_dir"]),
    }


def control_drill_config(workdir: str | Path, *, max_steps: int = 6,
                         save_every: int = 2, log_every: int = 1,
                         alerts: Optional[list] = None,
                         watchdog_seconds: float = 0.0) -> dict[str, Any]:
    """The control drill's tiny-llama config: the elastic drill config plus
    the fleet control plane (consensus control word), the fleet beacon
    plane (dying final beacons), and — for the hang leg — the armed hang
    watchdog.  Synchronous checkpointing: the hang leg ``os._exit``\\ s, so
    the last good save must already be committed, not in flight."""
    cfg = tiny_llama_config(workdir, max_steps=max_steps,
                            save_every=save_every)
    cfg["trainer"]["log_every_n_steps"] = log_every
    cfg["exp_manager"]["checkpoint_callback_params"][
        "async_checkpointing"] = False
    tel = cfg["exp_manager"]["telemetry"]
    tel["control"] = {"enabled": True}
    tel["fleet"] = {"enabled": True, "stale_after_seconds": 300.0}
    if alerts:
        tel["alerts"] = alerts
    if watchdog_seconds > 0:
        tel["health"] = {"watchdog_timeout_seconds": watchdog_seconds,
                         "watchdog_abort": False}
    return cfg


def run_control_drill(workdir: str | Path, *, world: int = 4,
                      total_steps: int = 6, save_every: int = 2,
                      hang_timeout_seconds: float = 240.0) -> dict[str, Any]:
    """The fleet-control acceptance drill (docs/observability.md "Fleet
    control") — the two ISSUE scenarios on the virtual CPU mesh:

    **Consensus stop** — an ``action: halt`` alert firing on ONE simulated
    host's non-replicated metric (``data_wait``, a span only that host
    times) must stop ALL hosts at the same deterministic boundary with a
    drained emergency save and the stop reason in ``run_summary.json``.
    Three legs: the host where the alert fires locally; a second simulated
    host that sees ONLY the folded control word (the ``peer_words`` seam)
    and must stop at the same boundary step with source ``fleet``; and the
    resumed incarnation proving loss-trajectory continuity to the control
    run.

    **Collective-hang escape** — a subprocess incarnation whose boundary
    sync hangs (``FaultInjector(mode="hang", phase="sync")`` — the dead
    peer mid-collective) must exit with the tagged ``EXIT_HANG_ESCAPE``
    code within the watchdog timeout, leaving the ``hang_<step>/`` bundle,
    a dying final beacon, and the control-trail exit note; the restarted
    incarnation resumes from the last good save with loss continuity.
    """
    import subprocess

    import jax

    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.control import (
        CONDITION_BITS,
        EXIT_ALERT_HALT,
        EXIT_HANG_ESCAPE,
        exit_code_for_stop,
    )

    devices = jax.devices()
    if world > len(devices):
        raise ValueError(f"drill wants {world} devices, have {len(devices)}")
    workdir = Path(workdir)
    halt_alert = [{"metric": "data_wait", "threshold": 1e-12,
                   "action": "halt", "name": "dw"}]

    # 0. control: an uninterrupted run for the continuity bar
    control = run_segment(
        tiny_llama_config(workdir / "control", max_steps=total_steps,
                          save_every=save_every),
        devices[:world])
    assert control.get("metrics"), "control run produced no metrics"
    control_losses = read_losses(control["run_dir"])

    # 1a. consensus stop, deciding host: the alert fires on THIS host's
    # non-replicated data_wait span; the stop folds through the control
    # word and takes the drained emergency save at the same boundary
    local_cfg = control_drill_config(workdir / "consensus",
                                     max_steps=total_steps,
                                     save_every=save_every,
                                     alerts=halt_alert)
    local = run_segment(local_cfg, devices[:world])
    t = local["trainer"]
    assert t.stop_class == "alert_halt", t.stop_class
    assert exit_code_for_stop(t.stop_class) == EXIT_ALERT_HALT
    stop_step = int(t.step)
    rs = json.loads(
        (Path(local["run_dir"]) / "run_summary.json").read_text())
    assert rs["elastic"]["stop_reason"].startswith("alert dw:"), rs["elastic"]
    assert rs["elastic"]["stop_class"] == "alert_halt", rs["elastic"]
    decisions = rs["control"]["decisions"]
    assert decisions and decisions[-1]["conditions"] == ["alert_halt"], (
        decisions)
    assert decisions[-1]["step"] == stop_step and decisions[-1]["stop"], (
        decisions)
    ck_dir = Path(local["run_dir"]) / "checkpoints"
    assert str(stop_step) in {p.name for p in ck_dir.iterdir()}, (
        f"no drained emergency save at stop step {stop_step}: "
        f"{sorted(p.name for p in ck_dir.iterdir())}")

    # 1b. consensus stop, OTHER host: no local condition at all — only the
    # folded control word (peer_words stands in for the deciding host's
    # contribution).  Must stop at the SAME deterministic boundary step,
    # with an emergency save and the honest "fleet consensus" reason.
    peer_cfg = control_drill_config(workdir / "peer", max_steps=total_steps,
                                    save_every=save_every)
    peer = run_segment(peer_cfg, devices[:world],
                       peer_words=lambda: CONDITION_BITS["alert_halt"])
    pt = peer["trainer"]
    assert int(pt.step) == stop_step, (
        f"peer host stopped at step {pt.step}, deciding host at "
        f"{stop_step} — NOT the same boundary")
    prs = json.loads(
        (Path(peer["run_dir"]) / "run_summary.json").read_text())
    assert prs["elastic"]["stop_reason"].startswith("fleet consensus:"), (
        prs["elastic"])
    pdec = prs["control"]["decisions"][-1]
    assert pdec["source"] == "fleet" and pdec["step"] == stop_step, pdec
    pck = Path(peer["run_dir"]) / "checkpoints"
    assert str(stop_step) in {p.name for p in pck.iterdir()}, (
        "peer host took no emergency save")

    # 1c. the resumed incarnation (alert disarmed — the operator fixed the
    # condition) continues from the emergency save to the horizon with
    # loss-trajectory continuity vs the uninterrupted control
    resume_cfg = control_drill_config(workdir / "consensus",
                                      max_steps=total_steps,
                                      save_every=save_every)
    resumed = run_segment(resume_cfg, devices[:world])
    assert resumed.get("metrics"), "resumed run produced no metrics"
    drill_losses = read_losses(resumed["run_dir"])
    common = sorted(set(control_losses) & set(drill_losses))
    assert common and max(common) == total_steps, (
        f"resumed run never reached step {total_steps}: "
        f"{sorted(drill_losses)}")
    worst = max(abs(control_losses[s] - drill_losses[s]) for s in common)
    assert worst == 0.0, (
        f"same-world consensus resume must be bitwise: max |Δloss| "
        f"{worst:.3e} over steps {common}")

    # 2. collective-hang escape: the doomed incarnation runs in a CHILD
    # process (the escape is a real os._exit) with its boundary sync hung
    # at step 4 — the watchdog must exit EXIT_HANG_ESCAPE well before the
    # injected 60 s sleep ends
    hang_cfg = control_drill_config(workdir / "hang", max_steps=total_steps,
                                    save_every=save_every,
                                    watchdog_seconds=2.0)
    cfg_path = workdir / "hang_cfg.json"
    cfg_path.write_text(json.dumps(hang_cfg))
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--hang-child",
         str(cfg_path), "--world", str(world), "--at-step", "4"],
        timeout=hang_timeout_seconds, capture_output=True, text=True,
    )
    assert child.returncode == EXIT_HANG_ESCAPE, (
        f"hung incarnation exited {child.returncode}, want "
        f"EXIT_HANG_ESCAPE={EXIT_HANG_ESCAPE}\n--- child stderr ---\n"
        + child.stderr[-2000:])
    hang_run = _run_dir(load_config(hang_cfg))
    bundles = sorted(p.name for p in hang_run.glob("hang_*"))
    assert bundles, f"no hang_<step>/ bundle in {hang_run}"
    beacons = [json.loads(l) for l in
               (hang_run / "fleet" / "host_0.jsonl").read_text().splitlines()]
    assert beacons and "hang escape" in str(
        beacons[-1].get("last_exception")), (
        f"final beacon is not a dying one: {beacons[-1]}")
    hrs = json.loads((hang_run / "run_summary.json").read_text())
    hdec = hrs["control"]["decisions"][-1]
    assert hdec["conditions"] == ["hang_escape"] and hdec.get("exit"), hdec

    # 3. the restarted incarnation resumes from the last good save and
    # finishes with loss continuity — the orchestrator's restart IS the
    # recovery, exactly as elastic resume promises
    hang_resumed = run_segment(
        control_drill_config(workdir / "hang", max_steps=total_steps,
                             save_every=save_every),
        devices[:world])
    assert hang_resumed.get("metrics"), "hang-resumed run has no metrics"
    hlosses = read_losses(hang_resumed["run_dir"])
    hcommon = sorted(set(control_losses) & set(hlosses))
    assert hcommon and max(hcommon) == total_steps, sorted(hlosses)
    hworst = max(abs(control_losses[s] - hlosses[s]) for s in hcommon)
    assert hworst == 0.0, (
        f"post-hang-escape resume diverged: max |Δloss| {hworst:.3e}")

    import time

    return {
        "ok": True,
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "world": world,
        "total_steps": total_steps,
        "consensus_stop_step": stop_step,
        "consensus_sources": ["local", "fleet"],
        "hang_escape_code": int(child.returncode),
        "hang_bundle": bundles[0],
        "max_loss_diff": max(worst, hworst),
        "run_dir": str(resumed["run_dir"]),
    }


def _hang_child(cfg_path: str, world: int, at_step: int) -> int:
    """The doomed incarnation of the hang leg (runs in a subprocess): its
    boundary sync blocks via ``FaultInjector(mode="hang", phase="sync")``;
    the armed watchdog must dump, beacon, and ``os._exit(EXIT_HANG_ESCAPE)``
    — so reaching the end of this function is itself a drill failure."""
    import jax

    from neuronx_distributed_training_tpu.trainer.elastic import FaultInjector

    raw = json.loads(Path(cfg_path).read_text())
    fault = FaultInjector(at_step=at_step, mode="hang", phase="sync",
                          hang_seconds=60.0)
    run_segment(raw, jax.devices()[:world], fault=fault)
    logger.error("hang child SURVIVED the hung sync — watchdog escape "
                 "did not fire")
    return 3


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: the canonical dp 4 -> 2 kill drill PLUS "
                         "a byte-flip corruption leg in a temp dir (single "
                         "process, virtual CPU devices)")
    ap.add_argument("--control-smoke", action="store_true",
                    help="fleet-control acceptance drill (docs/observability"
                         ".md 'Fleet control'): a halt alert on ONE "
                         "simulated host's non-replicated metric stops all "
                         "hosts at the same step with a drained emergency "
                         "save, and a hung boundary sync exits the process "
                         "with the tagged EXIT_HANG_ESCAPE code before "
                         "resuming cleanly")
    ap.add_argument("--hang-child", default=None, metavar="CFG_JSON",
                    help=argparse.SUPPRESS)  # internal: the hang leg's
    #                                          subprocess incarnation
    ap.add_argument("--corrupt", default=None, metavar="KIND",
                    help="run the corruption drill instead of the fault "
                         "drill: corrupt the completed run's newest "
                         "checkpoint with KIND (byte_flip/truncate/"
                         "delete_item/stale_sidecar) and prove quarantine + "
                         "walk-back + replan-off-the-verified-step")
    ap.add_argument("--at-step", type=int, default=3)
    ap.add_argument("--phase", choices=["step", "save", "restore"],
                    default="step")
    ap.add_argument("--mode", choices=["kill", "sigterm"], default="kill")
    ap.add_argument("--world", type=int, default=4,
                    help="device count of the original run")
    ap.add_argument("--resume-world", type=int, default=2,
                    help="device count after the 'preemption' (different "
                         "value triggers the restart-time replan)")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--save-every", type=int, default=2)
    ap.add_argument("--loss-tol", type=float, default=DEFAULT_LOSS_TOL)
    ap.add_argument("--workdir", default=None,
                    help="drill working dir (default: a fresh temp dir)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the drill report as JSON ('-' = stdout, "
                         "last line, tools/_jsonout contract)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    # CPU-only by design: the drill forks a child trainer (the hang escape),
    # and a parent holding a chip would starve it.  Force the 8-device
    # virtual CPU platform BEFORE jax initializes devices; the child comes
    # through this same main() and does the same.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    if args.hang_child is not None:
        return _hang_child(args.hang_child, args.world, args.at_step)

    workdir = args.workdir
    if workdir is None:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="nxdt_elastic_drill_")
    try:
        if args.control_smoke:
            # no --loss-tol here: every control-drill leg resumes at the
            # SAME world size, so the continuity bar is bitwise
            report = run_control_drill(
                workdir, world=args.world, total_steps=args.steps,
                save_every=args.save_every,
            )
        elif args.corrupt is not None:
            report = run_corruption_drill(
                workdir, kind=args.corrupt,
                world=args.world, resume_world=args.resume_world,
                total_steps=args.steps, save_every=args.save_every,
                loss_tol=args.loss_tol,
            )
        else:
            report = run_drill(
                workdir,
                at_step=args.at_step, phase=args.phase, mode=args.mode,
                world=args.world, resume_world=args.resume_world,
                total_steps=args.steps, save_every=args.save_every,
                loss_tol=args.loss_tol,
            )
            if args.smoke:
                # the --smoke CI gate grows a corruption leg: newest step
                # byte-flipped, auto-resume must quarantine + walk back +
                # replan off the verified step (docs/elasticity.md)
                corruption = run_corruption_drill(
                    Path(workdir) / "corruption", kind="byte_flip",
                    world=args.world, resume_world=args.resume_world,
                    total_steps=args.steps, save_every=args.save_every,
                    loss_tol=args.loss_tol,
                )
                report["integrity"] = {
                    k: corruption.get(k)
                    for k in ("kind", "corrupted_step", "resume_step",
                              "walked_back", "max_loss_diff")
                }
    except AssertionError as e:
        logger.error("drill FAILED: %s", e)
        if args.json:
            from _jsonout import write_json

            write_json({"ok": False, "error": str(e)}, args.json)
        return 1
    if args.control_smoke:
        logger.info(
            "control drill OK: consensus stop at step %d on both simulated "
            "hosts (sources %s), hang escape exited %d with bundle %s, "
            "resumed to step %d bitwise (max |Δloss| %.1e)",
            report["consensus_stop_step"], report["consensus_sources"],
            report["hang_escape_code"], report["hang_bundle"],
            report["total_steps"], report["max_loss_diff"],
        )
    elif args.corrupt is not None:
        logger.info(
            "corruption drill OK (%s): step %d corrupted -> quarantined, "
            "resumed %d -> %d devices from step %d (walked back %d); "
            "max |Δloss| %.2e",
            report["kind"], report["corrupted_step"], report["world"],
            report["resume_world"], report["resume_step"],
            report["walked_back"], report["max_loss_diff"],
        )
    else:
        logger.info(
            "drill OK: killed at step %d (%s/%s), resumed %d -> %d devices "
            "from step %d; max |Δloss| %.2e, restart cost %.2fs, goodput %.4f",
            report["at_step"], report["mode"], report["phase"], report["world"],
            report["resume_world"], report["resume_step"],
            report["max_loss_diff"], report["restart_cost_seconds"],
            report["goodput_fraction"] or 0.0,
        )
        if args.smoke and report.get("integrity"):
            logger.info(
                "corruption leg OK: %s at step %s -> walked back to %s",
                report["integrity"]["kind"],
                report["integrity"]["corrupted_step"],
                report["integrity"]["resume_step"],
            )
    if args.json:
        from _jsonout import write_json

        write_json(report, args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
