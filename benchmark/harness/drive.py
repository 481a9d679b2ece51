"""One run of one cell: build the trainer the way a user does, time its
``fit()`` from outside, then hold what it did against the plain reference.

The benchmark's own clock, not the program's: ``StepClock`` is called by the
trainer's metric sink once per logged step, after the step's blocking fetch
(cells log every step), and stamps ``time.perf_counter()``.  The same hook
reads, during set-up, what the check compares (the optimizer's first
gradient after step 1, the parameters' change after the last checked step)
and ends ``fit()`` when the window is over.
"""

from __future__ import annotations

import copy
import gc
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

from benchmark import flops
from benchmark.harness import say
from benchmark.harness import check as checks
from benchmark.harness import traffic as traffic_gen
from benchmark.harness.cell import ROOT, Cell
from benchmark.harness.stats import quantile95

#: run directories live inside the checkout (``.gitignore`` lists it)
WORK = ROOT / ".scratch" / "benchmark"
#: the LR schedule's horizon and the loop's ceiling; a run stops on its window
MAX_STEPS = 100000


def overrides_for(cell: Cell, seed: int, trace: bool, run_dir: Path) -> dict:
    t = cell.traffic
    ov = {
        "seed": int(seed),
        "exp_manager.exp_dir": str(run_dir),
        "trainer.max_steps": MAX_STEPS,
        "trainer.log_every_n_steps": 1,
        "data.seq_length": int(t["seq_length"]),
        "data.micro_batch_size": int(t["micro_batch_size"]),
        "data.global_batch_size": int(t["global_batch_size"]),
    }
    ov.update(t.get("overrides") or {})
    if trace:
        first = int(t["check_steps"]) + int(t["warmup_steps"]) + 1
        ov["exp_manager.telemetry.trace"] = {
            "enabled": True, "start_step": first,
            "num_steps": int(t["trace_steps"]), "keep_raw": True}
    return ov


def merged_config(cell: Cell, overrides: dict) -> dict:
    """The configuration as it is run: the file's ``trainer_config`` with the
    traffic's and the run's dotted overrides set."""
    cfg = copy.deepcopy(cell.config["trainer_config"])
    for dotted, value in overrides.items():
        node = cfg
        *parents, last = dotted.split(".")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = value
    return cfg


def check_tokens(cell: Cell, model: dict, seed: int) -> list:
    """Tokens of the checked steps, ``[micro, rows, seq]`` each: the rows the
    trainer's sequential sampler hands it for those steps."""
    t = cell.traffic
    return [traffic_gen.step_tokens(
        t["tokens"], seed, k, seq_len=int(t["seq_length"]),
        vocab=int(model["vocab_size"]),
        global_batch=int(t["global_batch_size"]),
        micro_batches=int(t["micro_batches"]))
        for k in range(int(t["check_steps"]))]


def print_cuts(cell: Cell) -> None:
    c = cell.config
    say(f"cell {cell.name}: config {cell.config_name} ({c['source']}), "
        f"traffic {cell.traffic_name}, {cell.chips} chip(s)")
    say(f"  why: {cell.why}")
    for k, v in c["reduced"].items():
        say(f"  cut: {k}: {v}")
    for k, v in c["assumed"].items():
        say(f"  assumed: {k}: {v}")
    say(f"  stands for: {c['deployment']}")
    say(f"  modules: reference {cell.reference.__name__} "
        f"({cell.reference.__file__}), operations {cell.operations.__name__} "
        f"({cell.operations.__file__})")
    say(f"  traffic: {cell.traffic['why']}")


class StepClock:
    """The metric sink's wrapper.  ``steps`` are 1-based as the trainer logs
    them; the window opens when step ``check + warmup`` completes."""

    def __init__(self, trainer, cell: Cell, model: dict, seconds: float,
                 seed: int):
        self.trainer = trainer
        self.seconds = float(seconds)
        self.check_steps = int(cell.traffic["check_steps"])
        self.open_step = self.check_steps + int(cell.traffic["warmup_steps"])
        self.model, self.seed = model, seed
        self.reference = cell.reference
        self.stamps: dict[int, float] = {}
        self.losses: dict[int, float] = {}
        self.grad_norms: dict[int, float] = {}
        self.grad1: Optional[dict] = None
        self.dparam: Optional[dict] = None
        self.window_open: Optional[float] = None
        self.last_inside: Optional[int] = None
        self.overran: Optional[int] = None
        self.read_seconds = 0.0  # set-up spent reading what the check compares
        self._inner = trainer.exp.log_metrics
        trainer.exp.log_metrics = self  # the sink fit() calls at each boundary

    def __call__(self, step: int, metrics: dict, **kw: Any) -> None:
        now = time.perf_counter()
        self._inner(step, metrics, **kw)
        if step in self.stamps:
            return
        self.stamps[step] = now
        self.losses[step] = float(metrics.get("loss", math.nan))
        self.grad_norms[step] = float(metrics.get("grad_norm", math.nan))
        if step == 1:
            self.grad1 = checks.first_gradient_norms(
                self.reference, self.trainer.opt_state,
                float(self.model["optim"]["betas"][0]))
            self.read_seconds += time.perf_counter() - now
        if step == self.check_steps:
            t_read = time.perf_counter()
            self.dparam = checks.parameter_change_norms(
                self.reference, self.trainer.params, self.model, self.seed)
            self.read_seconds += time.perf_counter() - t_read
        if step == self.open_step:
            # the reads above are set-up; the window opens after them
            self.window_open = time.perf_counter()
            self.stamps[step] = self.window_open
        elif self.window_open is not None:
            if now - self.window_open <= self.seconds:
                self.last_inside = step
            else:
                self.overran = step
                self.trainer.max_steps = self.trainer.step  # ends fit()

    # -- what the window held ---------------------------------------------

    def window_steps(self) -> list[int]:
        if self.last_inside is None:
            return []
        return list(range(self.open_step + 1, self.last_inside + 1))

    def step_seconds(self) -> list[float]:
        return [self.stamps[s] - self.stamps[s - 1] for s in self.window_steps()]

    def window_seconds(self) -> float:
        steps = self.window_steps()
        return self.stamps[steps[-1]] - self.window_open if steps else 0.0


def device_report(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             tamper: Optional[Callable] = None,
             overrides: Optional[dict] = None,
             limits: Optional[dict] = None,
             keep_xplane: Optional[Path] = None) -> dict:
    """Run the cell once and return the result object (also printed by
    ``run.py`` as the last line).  ``require_tpu=False`` and ``tamper`` are
    for the tests under ``tests/benchmark``: the first lifts the refusal to
    run off a TPU (device metrics are then left out), the second is handed
    the built trainer to break the timed path underneath.  ``overrides``
    (dotted, applied last) is how ``tools/readings.py`` and the tests switch
    the program's own lower-precision regime on: the control.  ``limits``
    replaces the configuration's limits: the tests run toy widths, whose
    gaps are wider than the cells' (fewer terms to average the rounding)."""
    t_import = time.perf_counter()
    import jax

    t_jax = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        sys.exit(f"benchmark: needs a TPU; JAX found {platform} "
                 f"({devices[0].device_kind}). Nothing was run.")
    if len(devices) < cell.chips:
        sys.exit(f"benchmark: cell {cell.name} needs {cell.chips} chip(s), "
                 f"JAX found {len(devices)}. Nothing was run.")
    devices = devices[:cell.chips]
    on_chip = platform == "tpu"
    peaks = flops.peaks_for(devices[0].device_kind) if on_chip else None

    from neuronx_distributed_training_tpu.config.loader import (
        batch_schedule,
        load_config,
    )
    from neuronx_distributed_training_tpu.data.loader import DataModule
    from neuronx_distributed_training_tpu.trainer.loop import Trainer
    from neuronx_distributed_training_tpu.utils.compile_cache import (
        configure_compilation_cache,
    )

    cache_dir = configure_compilation_cache()
    say(f"device: {len(devices)} x {devices[0].device_kind} ({platform}), "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    say(f"set-up: python and benchmark imports {t_import - t_process:.2f}s, "
        f"import jax {t_jax - t_import:.2f}s, reaching the chip "
        f"{t_devices - t_jax:.2f}s, program imports "
        f"{time.perf_counter() - t_devices:.2f}s")
    print_cuts(cell)

    t = cell.traffic
    seq, gbs = int(t["seq_length"]), int(t["global_batch_size"])
    vocab = int(cell.model["vocab_size"])
    run_dir = WORK / f"{cell.name}-{'trace' if trace else 'run'}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    try:
        as_run = merged_config(cell, {
            **overrides_for(cell, seed, trace, run_dir), **(overrides or {})})
        model = as_run["model"]
        cfg = load_config(as_run)
        sched = batch_schedule(cfg, len(devices))
        if int(sched["num_microbatches"]) != int(t["micro_batches"]):
            raise ValueError(
                f"traffic {cell.traffic_name} states {t['micro_batches']} "
                f"micro-batches; the trainer schedules {sched['num_microbatches']}")

        class SeededRows(DataModule):
            """The trainer's sampler, gather and prefetch over the traffic
            generator's rows."""

            def fetch_rows(self, idx):
                return {"input_ids": traffic_gen.token_rows(
                    t["tokens"], seed, idx, seq, vocab)}

        t_build = time.perf_counter()
        trainer = Trainer.from_config(
            cfg, data_module=SeededRows(1 << 24, gbs), devices=list(devices),
            enable_checkpointing=False)
        say(f"set-up: to trainer build {t_build - t_process:.2f}s, trainer "
            f"build (mesh, parameters and optimizer state on device, "
            f"experiment manager) {time.perf_counter() - t_build:.2f}s")
        if tamper is not None:
            tamper(trainer)
        clock = StepClock(trainer, cell, model, seconds, seed)
        t_fit = time.perf_counter()
        trainer.fit()
        t_done = time.perf_counter()
        log_dir = Path(trainer.exp.log_dir)
        kernels = (trainer.train_step.as_text().count("tpu_custom_call")
                   if isinstance(trainer.train_step, jax.stages.Compiled) else 0)
        device = device_report(devices)
        # free the program's state before the reference makes its own
        clock.trainer = None
        trainer.exp.log_metrics = None
        trainer.params = trainer.opt_state = trainer.train_step = None
        del trainer
        gc.collect()

        # a step may have several rows (a trace that closes logs one of its own)
        rows: dict[int, dict] = {}
        with open(log_dir / "metrics.jsonl") as f:
            for r in map(json.loads, f):
                rows.setdefault(int(r["step"]), {}).update(r)
        with open(log_dir / "run_summary.json") as f:
            summary = json.load(f)
        if clock.window_open is None or not clock.window_steps():
            raise RuntimeError("the window closed before a step completed in it")
        setup_s = clock.window_open - t_process
        say(f"set-up: compile or cache read {summary.get('compile_seconds')}s, "
            f"first {clock.open_step} steps (checked and warm-up) to window "
            f"open {clock.window_open - t_fit:.2f}s (first step done after "
            f"{clock.stamps[1] - t_fit:.2f}s, reading gradient and parameter "
            f"norms {clock.read_seconds:.2f}s); setup_s {setup_s:.2f}")

        steps = clock.window_steps()
        dts = clock.step_seconds()
        window_s = clock.window_seconds()
        tokens = len(steps) * gbs * seq
        say(f"window: {len(steps)} steps ({tokens} tokens) in {window_s:.3f}s "
            f"of --seconds {seconds:g}; median step "
            f"{statistics.median(dts) * 1e3:.2f} ms; fit() returned "
            f"{t_done - clock.stamps[steps[-1]]:.2f}s after the last of them")

        # -- the check, outside the window, after the trainer is freed -----
        t_ref = time.perf_counter()
        ref = cell.reference.run(
            model, model["optim"], as_run["trainer"].get("gradient_clip_val"),
            check_tokens(cell, model, seed), seed, shard=checks.sharder(devices))
        say(f"reference: {clock.check_steps} float32 steps in "
            f"{time.perf_counter() - t_ref:.2f}s (not in setup_s)")
        program = {
            "loss": [clock.losses[k + 1] for k in range(clock.check_steps)],
            "grad_norm": [clock.grad_norms[k + 1] for k in range(clock.check_steps)],
            "grad1": clock.grad1, "dparam": clock.dparam}
        verdict, compared = checks.compare(
            program, ref,
            limits or checks.limits_for(cell.config_name, cell.root))
        facts = {
            "finite loss at every step": all(
                math.isfinite(clock.losses[s]) for s in clock.stamps),
            "no retrace": not summary.get("retrace_events"),
            "samples consumed == steps x global batch": all(
                int(rows[s]["consumed_samples"]) == s * gbs for s in steps),
        }
        if on_chip:
            facts["compiled step holds the Pallas kernels"] = kernels >= 3
        for what, ok in facts.items():
            say(f"check: {what}: {'ok' if ok else 'FAILED'}")
        correct = verdict and all(facts.values())

        failed = sum(1 for s in steps if not math.isfinite(clock.losses[s]))
        attempted = len(steps) + (1 if clock.overran is not None else 0)
        ctx = {
            "cell": cell, "rows": [rows[s] for s in steps], "summary": summary,
            "peaks": peaks, "log_dir": log_dir, "on_chip": on_chip,
            "chips": len(devices),
            "data_parallel": int(sched["dp_size"]), "keep_xplane": keep_xplane,
        }
        result: dict[str, Any] = {
            "correct": bool(correct), "attempted": attempted, "failed": failed,
            "metrics": {}, "device": device, "compared": compared}
        if not trace:
            per_chip = tokens / window_s / len(devices)
            need = cell.operations.train_flops_per_token(cell.model, seq)
            others = ", ".join(f"{k} {v:.6g}" for k, v in need.items() if k != "total")
            say(f"required operations per token: {need['total'] / 1e9:.4f} G "
                f"by {cell.operations.__name__} ({others})")
            say(f"step time: {len(dts)} samples, p95 {quantile95(dts) * 1e3:.2f} ms")
            for dt, s_ in sorted(zip(dts, steps), reverse=True)[:3]:
                spans = {k[5:]: round(float(v) * 1e3, 1)
                         for k, v in rows[s_].items() if k.startswith("time/")}
                say(f"  slowest: step {s_} took {dt * 1e3:.1f} ms; the trainer's "
                    f"host spans in it, ms: {spans}")
            values = {
                "tokens_per_s_per_chip": per_chip,
                "mfu_pct": (100.0 * per_chip * need["total"]
                            / peaks["bf16_flops_per_s"]) if on_chip else None,
                "step_ms_p95": quantile95(dts) * 1e3,
                "setup_s": setup_s,
            }
            for m in cell.end_to_end:
                if values.get(m["name"]) is not None:
                    result["metrics"][m["name"]] = {
                        "value": values[m["name"]], "unit": m["unit"]}
        else:
            from benchmark.harness import layers

            result.update(layers.read_all(ctx, result))
        # each number compared beside its limit: the last lines of stderr, and
        # the last key of the result's line
        result["compared"] = compared = result.pop("compared")
        broken = [f"check: {what}: FAILED" for what, ok in facts.items() if not ok]
        print(*checks.beside_limits(compared).values(), *broken, sep="\n",
              file=sys.stderr, flush=True)
        return result
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
