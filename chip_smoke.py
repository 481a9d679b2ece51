"""First proof that the trainer starts on the chip: ``python chip_smoke.py``.

One process, one TPU v5e chip, no children.  Drives the trainer's main path
through the entry point a user calls (``nxdt-train`` = ``trainer.cli.main``)
at the published widths of ``examples/conf/hf_llama_7B_config.yaml``, cut only
by what one 16 GB chip forces, and checks what comes out by the repo's own
means.  Phases, in order:

- *kernels*: the compiled Pallas flash kernel, forward and backward, against
  ``ops.attention.core_attention`` in float32, at the tiles training uses.
- *train*: a few steps through ``cli.main()``, one verified checkpoint, and a
  second ``cli.main()`` that resumes from it.

``--chips 4`` (run by hand on a four-chip host; the driver has one chip) runs
only the sharded path and what it is compared with: the same model on
tp2 x dp2 + sequence parallel + ZeRO-1 against one device, then Llama-3-8B
widths, which do not fit one chip.

Any failed check raises, so the exit code is non-zero and the result line is
not printed.  Without a TPU the script refuses to start.  The last line of
stdout is the result; everything before it is a record of one smoke run, not
a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
CONF = REPO / "examples" / "conf"
#: run directories (checkpoints, metrics) live inside the checkout, under a
#: directory .gitignore lists, and are removed at the start and the end
WORK = REPO / ".scratch" / "chip_smoke"

#: |flash - core| / max|core| allowed per tensor: the kernel runs on bf16
#: operands (training's dtype and tiles) against a float32 reference
KERNEL_TOL = 3e-2
#: parity leg (bf16 compute, different reduction order under tp/sp/dp)
LOSS_TOL = 3e-2          # absolute, on a loss near ln(vocab)
GRAD_NORM_RTOL = 5e-2    # relative
#: per-device bytes_in_use may differ from the mean by this share
SPREAD_BAND = 0.15


#: Llama-3 attention shapes: q heads, kv heads, head dim
KERNEL_HEADS = (32, 8, 128)
KERNEL_CASES = [
    # name, batch, seq, mask, segments, window, with_lse
    ("causal_gqa", 1, 8192, False, False, None, False),
    ("attention_mask_b2", 2, 4096, True, False, None, False),
    ("segment_ids_b2", 2, 4096, False, True, None, False),
    ("sliding_window_4096", 1, 8192, False, False, 4096, False),
    ("with_lse", 1, 8192, False, False, None, True),
]
#: what one 16 GB chip forces on hf_llama_7B_config.yaml (each printed)
ONE_CHIP_CUTS = {
    "model.num_layers": 2,
    "distributed_strategy.tensor_model_parallel_size": 1,
    "distributed_strategy.sequence_parallel": False,
    "data.global_batch_size": 1,
    "data.train_dir": None,
    "data.synthetic": True,
}
#: --chips 4: depth of the parity leg and of the Llama-3-8B leg
PARITY_LAYERS, LLAMA3_LAYERS = 1, 3
#: extra overrides for every trainer run: empty on the chip;
#: tests/test_chip_smoke.py rehearses the phases on CPU at toy widths
EXTRA: dict = {}


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: FAILED {what}")
    say(f"  ok: {what}")


# --------------------------------------------------------------------------
# phase: kernels
# --------------------------------------------------------------------------


def _ref_group(q, k, v, w, wl, mask_row, seg, *, window):
    """float32 reference for ONE kv head and its q-head group: output via the
    repo's ``core_attention``, lse by plain logsumexp over the same bias."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_training_tpu.ops import attention as A

    s = q.shape[1]
    bias = None
    if mask_row is not None:
        bias = A.padding_mask_bias(mask_row)
    if seg is not None:
        sb = A.segment_mask_bias(seg)
        bias = sb if bias is None else bias + sb

    def loss(q, k, v):
        o = A.core_attention(q, k, v, causal=True, sliding_window=window,
                             bias=bias)
        out = jnp.sum(o * w)
        if wl is None:
            return out, (o, None)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, A.repeat_kv(k, q.shape[2]))
        scores = scores / math.sqrt(q.shape[-1]) + A.causal_mask_bias(
            s, s, sliding_window=window)
        lse = jax.nn.logsumexp(scores, axis=-1)  # [b, h, s]
        return out + jnp.sum(lse * wl), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return o, lse, grads


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_training_tpu.ops import flash_attention as fa

    say("== phase kernels: compiled flash fwd+bwd vs core_attention (f32)")
    nh, nkv, d = KERNEL_HEADS
    group = nh // nkv
    cases = KERNEL_CASES
    ref_group = jax.jit(_ref_group, static_argnames=("window",))
    rng = np.random.default_rng(seed)
    for ci, (name, b, s, masked, segmented, window, with_lse) in enumerate(cases):
        ks = jax.random.split(jax.random.PRNGKey(seed + ci), 5)
        # bf16-representable values, so kernel and reference see the same
        # operands and the error is the kernel's own
        mk = lambda key, shape: jax.random.normal(  # noqa: E731
            key, shape, jnp.float32).astype(jnp.bfloat16)
        q, k, v = mk(ks[0], (b, s, nh, d)), mk(ks[1], (b, s, nkv, d)), \
            mk(ks[2], (b, s, nkv, d))
        w = jax.random.normal(ks[3], (b, s, nh, d), jnp.float32)
        wl = (jax.random.normal(ks[4], (b, nh, s), jnp.float32)
              if with_lse else None)
        mask = seg = None
        if masked:  # right padding, the HF contract; row 0 full
            lens = np.array([s] + list(rng.integers(s // 4, s, b - 1)))
            mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None],
                               jnp.int32)
        if segmented:  # contiguous packed records of uneven length
            rows = []
            for _ in range(b):
                cuts = np.sort(rng.choice(np.arange(1, s), 5, replace=False))
                rows.append(np.searchsorted(cuts, np.arange(s), side="right"))
            seg = jnp.asarray(np.stack(rows), jnp.int32)

        # w/wl/mask/seg are arguments, not closure constants: a captured
        # array is baked into the executable (hundreds of MB here)
        def flash_loss(q, k, v, w, wl, mask, seg):
            if with_lse:
                o, lse = fa.flash_attention_with_lse(
                    q, k, v, causal=True, sliding_window=window,
                    interpret=False)
                return (jnp.sum(o.astype(jnp.float32) * w)
                        + jnp.sum(lse * wl)), (o, lse)
            o = fa.flash_attention(
                q, k, v, causal=True, sliding_window=window,
                attention_mask=mask, segment_ids=seg, interpret=False)
            return jnp.sum(o.astype(jnp.float32) * w), (o, None)

        t0 = time.perf_counter()
        jf = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                        has_aux=True))
        compiled = jf.lower(q, k, v, w, wl, mask, seg).compile()
        n_calls = compiled.as_text().count("tpu_custom_call")
        check(n_calls >= 3, f"{name}: fwd, dq and dkv are Mosaic kernels "
                            f"({n_calls} tpu_custom_call in the compiled text)")
        (_, (o, lse)), (dq, dk, dv) = compiled(q, k, v, w, wl, mask, seg)
        jax.block_until_ready(dq)
        secs = time.perf_counter() - t0

        # reference, one kv head (and its q-head group) at a time: the full
        # [b, 32, s, s] float32 score tensor does not fit beside the kernel
        worst: dict[str, tuple[float, float]] = {}
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            for g in range(nkv):
                hs = slice(g * group, (g + 1) * group)
                ro, rlse, (rdq, rdk, rdv) = ref_group(
                    q32[:, :, hs], k32[:, :, g:g + 1], v32[:, :, g:g + 1],
                    w[:, :, hs], None if wl is None else wl[:, hs],
                    mask, seg, window=window)
                pairs = {"o": (o[:, :, hs], ro), "dq": (dq[:, :, hs], rdq),
                         "dk": (dk[:, :, g:g + 1], rdk),
                         "dv": (dv[:, :, g:g + 1], rdv)}
                if with_lse:
                    pairs["lse"] = (lse[:, hs], rlse)
                for key, (got, ref) in pairs.items():
                    got = got.astype(jnp.float32)
                    err = float(jnp.max(jnp.abs(got - ref)))
                    scale = float(jnp.max(jnp.abs(ref)))
                    e0, s0 = worst.get(key, (0.0, 0.0))
                    worst[key] = (max(e0, err), max(s0, scale))
        line = "  ".join(
            f"{key} abs {e:.3e} rel {e / max(sc, 1e-30):.3e}"
            for key, (e, sc) in worst.items())
        say(f"  {name} (b {b}, s {s}, {nh}/{nkv} heads, d {d}, bf16 "
            f"operands; compile+run {secs:.1f}s): {line}")
        for key, (e, sc) in worst.items():
            check(math.isfinite(e) and e <= KERNEL_TOL * sc,
                  f"{name}.{key} within {KERNEL_TOL:g} of max|ref|")
        del q, k, v, w, wl, o, lse, dq, dk, dv, q32, k32, v32, compiled, jf
        gc.collect()


# --------------------------------------------------------------------------
# driving the trainer and reading what it wrote
# --------------------------------------------------------------------------


def hbm_stats(device) -> dict:
    return device.memory_stats()


def read_metrics(log_dir: Path) -> list[dict]:
    with open(log_dir / "metrics.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def overrides_argv(ov: dict) -> list[str]:
    out = []
    for k, v in ov.items():
        out += ["--set", f"{k}={json.dumps(v) if not isinstance(v, str) else v}"]
    return out


def norm_scales(params) -> "dict[str, object]":
    """Host copies of the RMSNorm scales: small leaves that every step
    updates — the parameter checksum."""
    import jax
    import numpy as np

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat
            if "norm" in jax.tree_util.keystr(p)}


def observed_fit(trainer, fit) -> dict:
    """Run ``fit(trainer)`` and report what only the live trainer can show:
    whether the parameters moved, and the executable the loop ran."""
    import jax
    import numpy as np

    before = norm_scales(trainer.params)
    fit(trainer)
    after = norm_scales(trainer.params)
    aot = isinstance(trainer.train_step, jax.stages.Compiled)
    return {
        "changed": int(sum(np.sum(before[k] != after[k]) for k in before)),
        "checksum_elems": int(sum(v.size for v in before.values())),
        "log_dir": Path(trainer.exp.log_dir),
        "ckpt_dir": Path(trainer.exp.checkpoint_dir),
        "custom_calls": (trainer.train_step.as_text().count("tpu_custom_call")
                         if aot else 0),
    }


def check_observed(seen: dict, what: str) -> None:
    check(seen["changed"] > 0,
          f"{what}: parameter checksum moved ({seen['changed']} of "
          f"{seen['checksum_elems']} norm-scale elements changed)")
    check(seen["custom_calls"] >= 3,
          f"{what}: the AOT-compiled executable the loop ran holds the Pallas "
          f"kernels ({seen['custom_calls']} tpu_custom_call)")


def run_cli(config: Path, ov: dict) -> dict:
    """One ``nxdt-train`` invocation, in-process.  ``Trainer.fit`` is wrapped
    only to look at the trainer the CLI built (``observed_fit``)."""
    from neuronx_distributed_training_tpu.trainer import cli
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    seen: dict = {}
    orig_fit = Trainer.fit

    def fit(self):
        out = {}
        seen.update(observed_fit(
            self, lambda t: out.update(metrics=orig_fit(t))))
        return out["metrics"]

    Trainer.fit = fit
    argv = sys.argv
    sys.argv = ["nxdt-train", "--config", str(config)] + overrides_argv(ov)
    try:
        cli.main()
    finally:
        sys.argv = argv
        Trainer.fit = orig_fit
    gc.collect()  # the trainer (params + optimizer state) is garbage now
    return seen


def init_loss(cfg) -> float:
    """Cross-entropy a freshly initialized model gives on random tokens:
    ln(vocab) plus half the variance of its logits.  The final RMSNorm has
    unit RMS and the untied head is N(0, initializer_range^2), so the logits
    have variance hidden_size x initializer_range^2 — 0.82 nats at hidden
    4096, which a bare ln(vocab) would miss."""
    m = cfg["model"]
    sigma2 = int(m["hidden_size"]) * float(m.get("initializer_range", 0.02)) ** 2
    return math.log(int(m["vocab_size"])) + sigma2 / 2


def check_steps(rows: list[dict], *, want_loss: float, gbs: int,
                first_step: int, what: str) -> None:
    for r in rows:
        say(f"  step {r['step']}: loss {r['loss']:.4f} grad_norm "
            f"{r['grad_norm']:.4f} lr {r['lr']:.3g} step_time "
            f"{r['step_time']:.3f}s consumed_samples "
            f"{int(r['consumed_samples'])}")
    check(all(math.isfinite(r["loss"]) for r in rows),
          f"{what}: loss finite at every step")
    check(all(math.isfinite(r["grad_norm"]) and r["grad_norm"] > 0
              for r in rows), f"{what}: grad norm finite and non-zero")
    check([r["step"] for r in rows]
          == list(range(first_step, first_step + len(rows))),
          f"{what}: steps {first_step}..{first_step + len(rows) - 1} logged")
    check(all(int(r["consumed_samples"]) == r["step"] * gbs for r in rows),
          f"{what}: consumed_samples == step x gbs ({gbs})")
    if first_step == 1:
        check(abs(rows[0]["loss"] - want_loss) <= 0.5,
              f"{what}: step-1 loss {rows[0]['loss']:.3f} within 0.5 of "
              f"ln(vocab) + logit variance / 2 = {want_loss:.3f}")


def phase_train(seed: int) -> None:
    import dataclasses

    import jax

    from neuronx_distributed_training_tpu.checkpoint import (
        CheckpointConfig,
        Checkpointer,
    )
    from neuronx_distributed_training_tpu.config.loader import load_config

    say("== phase train: nxdt-train on hf_llama_7B_config.yaml, one chip")
    steps, gbs = 4, ONE_CHIP_CUTS["data.global_batch_size"]
    say("  published widths kept: hidden 4096, ffn 11008, 32 heads, d 128, "
        "vocab 32000, seq 4096, flash, selective recompute, mixed_precision")
    say("  cut: model.num_layers 32 -> 2 (fp32 master + AdamW state of 2 "
        "layers and the 32000-token embedding and head already hold 8.0 GB)")
    say("  cut: tensor_model_parallel_size 8 -> 1, sequence_parallel off "
        "(one chip)")
    say("  cut: global_batch_size 1024 -> 1: a second micro-batch adds the "
        "fp32 accumulation carry, and the TPU compiler then needs 16.06 GiB "
        "of the chip's 15.75 GiB")
    say(f"  cut: data.train_dir unset, data.synthetic true (random tokens "
        f"from seed {seed}); "
        f"{steps} steps, then 2 more after a resume")
    ov = {
        **ONE_CHIP_CUTS,
        **EXTRA,
        "seed": seed,
        "exp_manager.exp_dir": str(WORK / "train"),
        "trainer.max_steps": steps,
        "trainer.log_every_n_steps": 1,
        "exp_manager.checkpoint_callback_params.every_n_train_steps": steps,
    }
    config = CONF / "hf_llama_7B_config.yaml"
    cfg = load_config(str(config), ov)
    want_loss = init_loss(cfg)
    t0 = time.perf_counter()
    seen = run_cli(config, ov)
    say(f"  first nxdt-train call: {time.perf_counter() - t0:.1f}s wall")
    rows = read_metrics(seen["log_dir"])
    check_steps(rows, want_loss=want_loss, gbs=gbs, first_step=1,
                what="train")
    check_observed(seen, "train")
    with open(seen["log_dir"] / "run_summary.json") as f:
        summary = json.load(f)
    say(f"  compile_seconds {summary.get('compile_seconds')}  "
        f"memory_analysis {summary.get('memory_analysis')}")
    check(not summary.get("retrace_events"),
          "train: no retrace after the first step (the loop ran one "
          "AOT-compiled executable; RecompileDetector saw one signature)")
    stats = hbm_stats(jax.devices()[0])
    say(f"  peak HBM {stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
        f"{stats['bytes_limit'] / 2**30:.2f} GiB "
        f"(memory_stats, whole process so far)")

    ck_cfg = dataclasses.replace(CheckpointConfig.from_config(cfg),
                                 dir=seen["ckpt_dir"])
    t0 = time.perf_counter()
    ck = Checkpointer(ck_cfg)
    try:
        check(ck.latest_step() == steps,
              f"train: checkpoint saved at the last step ({steps})")
        verdict = ck.verify_step(steps)
    finally:
        ck.close()
    check(verdict.status == "ok" and not verdict.failures,
          f"train: checkpoint {steps} verifies against its integrity "
          f"sidecar ({verdict.status}, {time.perf_counter() - t0:.1f}s)")

    say("  second nxdt-train call: auto-resume, 2 more steps")
    t0 = time.perf_counter()
    seen2 = run_cli(config, {**ov, "trainer.max_steps": steps + 2})
    say(f"  second nxdt-train call: {time.perf_counter() - t0:.1f}s wall")
    check(seen2["log_dir"] == seen["log_dir"],
          "resume: continued in the same run directory")
    rows2 = [r for r in read_metrics(seen2["log_dir"]) if r["step"] > steps]
    check(len(rows2) == 2, "resume: exactly two more steps logged")
    check_steps(rows2, want_loss=want_loss, gbs=gbs, first_step=steps + 1,
                what="resume")


# --------------------------------------------------------------------------
# --chips 4: the sharded path and what it is compared with
# --------------------------------------------------------------------------


def fit_once(config: Path, ov: dict, devices, *, spread: bool, gbs: int,
             what: str) -> list[dict]:
    """``Trainer.from_config(..., devices=...)`` -> ``fit()``; checks and
    returns the per-step rows.  The trainer is dropped before returning."""
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(str(config), ov)
    trainer = Trainer.from_config(cfg, devices=list(devices),
                                  enable_checkpointing=False)
    seen = observed_fit(trainer, Trainer.fit)
    check_observed(seen, what)
    if spread:
        assert_spread(trainer, devices)
    del trainer
    gc.collect()
    rows = read_metrics(seen["log_dir"])
    check_steps(rows, want_loss=init_loss(cfg), gbs=gbs, first_step=1,
                what=what)
    return rows


def assert_spread(trainer, devices) -> None:
    """Code that has only met one chip may put everything on the first."""
    import jax

    def largest(tree):
        return max(jax.tree_util.tree_leaves(tree), key=lambda x: x.size)

    for what, leaf, share in (
        ("largest parameter leaf", largest(trainer.params), 2),      # tp
        ("largest optimizer leaf", largest(
            {k: v for k, v in trainer.opt_state.items()
             if k in ("mu", "nu", "m", "v")} or trainer.opt_state), 4),  # tp x dp
    ):
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        per = {int(s.data.nbytes) for s in shards}
        say(f"  {what}: {leaf.dtype}{list(leaf.shape)} "
            f"{leaf.nbytes / 2**20:.0f} MiB, shard "
            f"{list(shards[0].data.shape)} on {len(devs)} devices")
        check(len(devs) == len(devices) == 4,
              f"{what} has shards on four distinct devices")
        check(per == {leaf.nbytes // share},
              f"{what}: every device holds 1/{share} of it")
    used = [hbm_stats(d)["bytes_in_use"] for d in devices]
    mean = sum(used) / len(used)
    say("  bytes_in_use per device: "
        + ", ".join(f"{u / 2**30:.2f} GiB" for u in used))
    check(all(abs(u - mean) <= SPREAD_BAND * mean for u in used),
          f"per-device bytes_in_use within {SPREAD_BAND:.0%} of their mean")


def phase_four_chips(seed: int, devices) -> None:
    steps = 5
    common = {
        **EXTRA,
        "seed": seed,
        "data.train_dir": None,
        "data.synthetic": True,
        "trainer.max_steps": steps,
        "trainer.log_every_n_steps": 1,
        # the published 100-step warm-up leaves lr ~ 0 for a 5-step run, and
        # parity of steps that do not move the parameters says little about
        # the sharded optimizer
        "model.optim.sched.warmup_steps": 2,
    }

    say("== parity leg: hf_llama_7B widths, tp2 x dp2 + SP + ZeRO-1 on four "
        "chips vs the same config on jax.devices()[:1]")
    gbs = 4
    say("  cut: model.num_layers 32 -> 1 (the one-device side must hold the "
        "same global batch, and two micro-batches at 2 layers need 16.06 GiB "
        "of 15.75 GiB); global_batch_size 1024 -> 4; warmup_steps 100 -> 2; "
        f"synthetic tokens from seed {seed}")
    config = CONF / "hf_llama_7B_config.yaml"
    base = {**common, "model.num_layers": PARITY_LAYERS,
            "data.global_batch_size": gbs}
    sharded = fit_once(config, {
        **base, "exp_manager.exp_dir": str(WORK / "parity4"),
        "distributed_strategy.tensor_model_parallel_size": 2,
        "distributed_strategy.sequence_parallel": True,
        "distributed_strategy.zero1": True,
    }, devices, spread=True, gbs=gbs, what="tp2xdp2")
    single = fit_once(config, {
        **base, "exp_manager.exp_dir": str(WORK / "parity1"),
        "distributed_strategy.tensor_model_parallel_size": 1,
        "distributed_strategy.sequence_parallel": False,
    }, devices[:1], spread=False, gbs=gbs, what="one device")
    check(len(sharded) == len(single) == steps, f"{steps} steps on both sides")
    for a, b in zip(sharded, single):
        dl = abs(a["loss"] - b["loss"])
        dg = abs(a["grad_norm"] - b["grad_norm"]) / b["grad_norm"]
        say(f"  step {a['step']}: |d loss| {dl:.2e}  grad_norm rel diff "
            f"{dg:.2e}")
        check(dl <= LOSS_TOL and dg <= GRAD_NORM_RTOL,
              f"step {a['step']} parity (loss within {LOSS_TOL:g}, grad "
              f"norm within {GRAD_NORM_RTOL:.0%})")

    say("== needs-four-chips leg: hf_llama3_8B widths, seq 8192, tp2 x dp2 + "
        "SP + ZeRO-1 + flash")
    gbs = 2
    say("  cut: tensor_model_parallel_size 32 -> 2 (dp 2 on four chips); "
        "model.num_layers 32 -> 3 (4 layers need 16.81 GiB of 15.75 GiB in "
        "the AOT compile for v5e:2x2; 3 need 14.35 GiB); global_batch_size "
        "1024 -> 2; warmup_steps -> 2; synthetic tokens")
    fit_once(CONF / "hf_llama3_8B_config.yaml", {
        **common, "exp_manager.exp_dir": str(WORK / "llama3"),
        "model.num_layers": LLAMA3_LAYERS,
        "distributed_strategy.tensor_model_parallel_size": 2,
        "data.global_batch_size": gbs,
    }, devices, spread=True, gbs=gbs, what="llama3-8B")


# --------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and what it is compared "
                         "with (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
                 f"({dev.device_kind}). Nothing was run.")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} devices. Nothing was run.")

    from neuronx_distributed_training_tpu.utils.compile_cache import (
        configure_compilation_cache,
    )

    cache = configure_compilation_cache()
    say(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}), "
        f"jax {jax.__version__}, compile cache {cache}")
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(args.seed, devices)
        else:
            phase_kernels(args.seed)
            phase_train(args.seed)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
