"""Times the parts of one Mamba-2 mixer on the attached chip at the benchmark's
shape (two sequences of 8192 tokens, 64 heads of 64, state 128, 8 groups, 6144
convolution channels): the scan (``ops/ssd.py``) at several block sizes, the
convolution with its bias and ``silu`` (``ops/short_conv.py::causal_conv``) and
the gated norm, each forward alone and forward + backward, beside the least
its bytes allow.  PERF.md section 6 (PR 46) keeps the table.

    python tools/time_mamba_mixer.py [--out chiprun_out/mamba_mixer.json]
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import flops  # noqa: E402
from neuronx_distributed_training_tpu.ops import norm, short_conv, ssd  # noqa: E402

B, S, H, P, G, N, K = 2, 8192, 64, 64, 8, 128, 4
CONV = H * P + 2 * G * N


def timed(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def both(fn, args, wrt):
    fwd = jax.jit(fn)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=wrt))
    return {"fwd_ms": timed(fwd, *args), "fwd_bwd_ms": timed(grad, *args)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/mamba_mixer.json")
    ap.add_argument("--profile", default=None,
                    help="a directory: trace the scan's forward + backward there and "
                         "print the device operations that take most of it")
    args = ap.parse_args()
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    x = jax.random.normal(k[0], (B, S, H, P), bf)
    bm = jax.random.normal(k[1], (B, S, G, N), bf)
    cm = jax.random.normal(k[2], (B, S, G, N), bf)
    dt = jax.random.normal(k[3], (B, S, H), bf)
    a_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    d, dt_bias = jnp.ones((H,)), jnp.full((H,), -4.0)
    tokens = B * S
    HBM = flops.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    scan_fwd = tokens * ssd.bytes_per_token(H, P, N, G)
    scan_bwd = tokens * (2 * (H * P + 2 * G * N + H) + H * P) * 2
    found = {"device": jax.devices()[0].device_kind,
             "least_ms": {"scan_fwd": scan_fwd / HBM * 1e3, "scan_bwd": scan_bwd / HBM * 1e3,
                          "conv_fwd": tokens * 2 * CONV * 2 / HBM * 1e3,
                          "conv_bwd": tokens * 3 * CONV * 2 / HBM * 1e3,
                          "gated_norm_fwd": tokens * 3 * H * P * 2 / HBM * 1e3,
                          "gated_norm_bwd": tokens * 5 * H * P * 2 / HBM * 1e3}}
    for blocks in (2, 4, 8, 16):
        ssd.BLOCK_CHUNKS = blocks
        try:
            found[f"scan_blocks_of_{blocks}"] = both(
                lambda *a: ssd.ssd_scan(*a), (x, bm, cm, dt, a_log, d, dt_bias), (0, 1, 2, 3))
        except Exception as e:  # noqa: BLE001 — a block too large for the chip is a finding
            found[f"scan_blocks_of_{blocks}"] = {"failed": f"{type(e).__name__}: {e}"[:200]}
        print(blocks, found[f"scan_blocks_of_{blocks}"], flush=True)
    if args.profile:
        from benchmark import trace_reduce

        ssd.BLOCK_CHUNKS = 8
        grad = jax.jit(jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a).astype(jnp.float32)),
                                argnums=(0, 1, 2, 3)))
        operands = (x, bm, cm, dt, a_log, d, dt_bias)
        timed(grad, *operands, reps=1)
        with jax.profiler.trace(args.profile):
            timed(grad, *operands, reps=3)
        print(trace_reduce.describe(trace_reduce.find_xplane(Path(args.profile)), top=30))
    xbc = jax.random.normal(k[4], (B, S, CONV), bf)
    taps, bias = jax.random.normal(k[5], (K, CONV), bf), jnp.zeros((CONV,), bf)
    found["conv_" + short_conv.CONV_WAY] = both(
        lambda xx, w, bb: short_conv.causal_conv(xx, w, bb, silu=True), (xbc, taps, bias),
        (0, 1, 2))
    y = jax.random.normal(k[6], (B, S, H * P), bf)
    z = jax.random.normal(k[7], (B, S, H * P), bf)
    scale = jnp.ones((H * P,), bf)
    found["gated_norm"] = both(
        lambda sc, yy, zz: norm.apply_gated_rms_norm({"scale": sc}, yy, zz, groups=G, eps=1e-5),
        (scale, y, z), (0, 1, 2))
    print(json.dumps(found, indent=1))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(found, indent=1))


if __name__ == "__main__":
    main()
