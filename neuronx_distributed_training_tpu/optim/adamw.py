"""AdamW with fp32 optimizer state, master weights, global-norm clipping, and
ZeRO-1 sharding specs.

The reference gets its optimizer from the NeMo registry (``adamw_fp32OptState``,
reference ``base.py:305``) and wraps it with NxD's ZeRO-1
``ZeroRedundancyOptimizer`` which shards optimizer state over DP ranks, clips
gradients internally, and all-gathers updated params (``base.py:127-143,
321-325``; ``nlp_overrides.py:203-216``).

TPU-native: the optimizer is a pure function; ZeRO-1 is *just a sharding spec* —
``opt_state_specs`` shards the fp32 moments/master weights over the compound DP
axis ``(data, expert)`` on a dimension the param spec leaves unsharded.  XLA's
weight-update sharding then performs exactly the reduce-scatter → sharded-update
→ all-gather dance the NxD wrapper hand-codes (cf. "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training", arXiv:2004.13336).

Grad clipping happens inside the update (global norm over the whole grad tree)
and the pre-clip ``grad_norm`` is returned for logging, matching the reference's
``log_gradient_norm`` semantics (``exp_manager.py``, ``base.py:227``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip_norm: Optional[float] = 1.0
    # params whose tree-path matches one of these substrings get no weight decay
    # (reference BaseHfModel: no decay on bias/norm params, base_model.py:18-54)
    no_decay_substrings: tuple = ("norm", "bias", "scale")

    @classmethod
    def from_config(cls, optim_cfg: dict[str, Any], trainer_cfg: dict[str, Any] | None = None,
                    do_layer_norm_weight_decay: bool = False) -> "AdamWConfig":
        o = dict(optim_cfg or {})
        t = dict(trainer_cfg or {})
        betas = o.get("betas", [0.9, 0.999])
        return cls(
            beta1=float(betas[0]),
            beta2=float(betas[1]),
            eps=float(o.get("eps", 1e-8)),
            weight_decay=float(o.get("weight_decay", 0.01)),
            grad_clip_norm=t.get("gradient_clip_val", 1.0),
            no_decay_substrings=() if do_layer_norm_weight_decay else ("norm", "bias", "scale"),
        )


@dataclasses.dataclass(frozen=True)
class EMAConfig:
    """Weight EMA (the reference's NeMo ``EMA`` callback wired from
    ``exp_manager.ema``, ``utils/exp_manager.py:298-305``).  TPU-native the
    EMA tree lives INSIDE the optimizer state so it is jitted, donated,
    ZeRO-1-sharded, and checkpointed with everything else."""

    decay: float = 0.9999
    apply_every_n_steps: int = 1
    start_step: int = 0
    evaluate_ema_weights_instead: bool = False

    @classmethod
    def from_config(cls, ema_cfg: dict[str, Any]) -> "EMAConfig":
        e = dict(ema_cfg or {})
        return cls(
            decay=float(e.get("decay", 0.9999)),
            apply_every_n_steps=int(e.get("apply_ema_every_n_steps", 1)),
            start_step=int(e.get("start_step", 0)),
            evaluate_ema_weights_instead=bool(
                e.get("evaluate_ema_weights_instead", False)
            ),
        )


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path).lower()


def decay_mask(params, cfg: AdamWConfig):
    """1.0 where weight decay applies, 0.0 for bias/norm-type params."""

    def leaf_mask(path, x):
        p = _path_str(path)
        if any(s in p for s in cfg.no_decay_substrings):
            return 0.0
        return 1.0

    return jax.tree_util.tree_map_with_path(leaf_mask, params)


#: scalar counters threaded through ``opt_state["health"]`` when the numerics
#: flight recorder is enabled: they ride the donated state step-to-step,
#: survive checkpoints, and reach the host for free inside the boundary
#: metric fetch (``last_nonfinite_step`` starts at -1 = "never")
HEALTH_STATE_KEYS = (
    "steps_seen", "nonfinite_count", "skipped_count", "last_nonfinite_step",
)


def init_health_state():
    return {
        "steps_seen": jnp.zeros((), jnp.int32),
        "nonfinite_count": jnp.zeros((), jnp.int32),
        "skipped_count": jnp.zeros((), jnp.int32),
        "last_nonfinite_step": jnp.full((), -1, jnp.int32),
    }


def init_opt_state(params, policy: DtypePolicy | None = None, *, ema: bool = False,
                   health: bool = False, tensorstats=None,
                   tensorstats_bucket_groups: tuple = ()):
    """Opt state: step counter, fp32 moments, fp32 master weights when the
    params themselves are stored in a lower precision, (optionally) the
    weight-EMA tree, (optionally) the numerics-health counters, and
    (optionally) the tensor-numerics-observatory cumulative record
    (``tensorstats`` — a ``telemetry.tensorstats.TensorStatsConfig``;
    ``tensorstats_bucket_groups`` names the ZeRO-1 bucket slots when the
    bucket phase is on)."""
    policy = policy or DtypePolicy()
    odt = policy.optimizer_dtype

    def zeros_like_in(x):
        return jnp.zeros(x.shape, odt)

    state = {
        "step": jnp.zeros((), jnp.int32),
        "mu": jax.tree_util.tree_map(zeros_like_in, params),
        "nu": jax.tree_util.tree_map(zeros_like_in, params),
    }
    if jnp.dtype(policy.param_dtype) != jnp.dtype(odt):
        state["master"] = jax.tree_util.tree_map(lambda x: x.astype(odt), params)
    if ema:
        state["ema"] = jax.tree_util.tree_map(lambda x: x.astype(odt), params)
    if health:
        state["health"] = init_health_state()
    if tensorstats is not None and getattr(tensorstats, "enabled", False):
        from neuronx_distributed_training_tpu.telemetry.tensorstats import (
            init_tensorstats_state,
        )

        state["tensorstats"] = init_tensorstats_state(
            tensorstats, params,
            bucket_groups=tuple(tensorstats_bucket_groups))
    return state


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def grouped_sq_norms(tree, group_fn: Callable) -> dict[str, jax.Array]:
    """Per-group sums of squares over a pytree (fp32).

    ``group_fn(path) -> str`` names each leaf's group.  The per-leaf squared
    sums are the SAME reductions ``global_norm`` performs — the caller derives
    the global norm as ``sqrt(sum(values))``, so grouped health norms and the
    clipping norm share one reduction pass (one source of truth)."""
    sums: dict[str, jax.Array] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = group_fn(path)
        s = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        sums[key] = sums[key] + s if key in sums else s
    return sums


def adamw_update(
    params,
    grads,
    opt_state,
    lr,
    cfg: AdamWConfig,
    policy: DtypePolicy | None = None,
    trainable_mask=None,
    ema_cfg: Optional[EMAConfig] = None,
    *,
    grad_group_fn: Optional[Callable] = None,
    skip_nonfinite: bool = False,
    extra_finite=None,
    bucket_plan=None,
    prefetch_ag: bool = True,
    tensorstats_cfg=None,
):
    """One AdamW step. Returns (new_params, new_opt_state, metrics).

    ``trainable_mask`` (pytree of 0/1, e.g. ``peft.lora.trainable_mask``)
    freezes masked-out params completely: no grad, no moment update, no weight
    decay — the LoRA/PEFT freeze.

    Numerics-health hooks (``telemetry.health``):

    - ``grad_group_fn(path) -> str``: when set, metrics gains ``group_norms``
      (per-layer-group pre-clip grad norms) and the global clipping norm is
      DERIVED from the same per-leaf squared sums — one reduction pass, not a
      second one.
    - ``skip_nonfinite=True``: the whole update (params, moments, master, EMA,
      step counter) is replaced leaf-wise by the incoming state when the
      update is non-finite — an in-graph ``select``, so a poisoned batch
      leaves params bitwise-unchanged with no recompile and no host
      round-trip (the grad-scaler-skip behavior without a dynamic scale).
    - ``extra_finite``: extra boolean ANDed into the finite flag (the caller
      passes loss finiteness so a NaN loss with, e.g., masked-to-zero grads
      still counts as a skip).

    ``metrics["updates_finite"]`` (bool) is reported whenever any hook is
    active.

    ``bucket_plan`` (``optim.overlap.BucketPlan``): the engineered-overlap
    path — the moment/master/param updates run per layer-group bucket with
    one combined parameter all-gather per bucket (and, under
    ``prefetch_ag``, an ``optimization_barrier`` chain staggering the
    buckets so gather k overlaps update k+1).  Everything before (norms,
    clipping) and after (EMA, skip select, metrics) is the shared
    whole-tree code, and the per-bucket lambdas are the SAME ones the
    monolithic path maps — numerics are bitwise identical; only the
    collective structure changes.

    ``tensorstats_cfg`` (``telemetry.tensorstats.TensorStatsConfig``,
    enabled): the tensor numerics observatory — per layer-group absmax /
    rms / zero / subnormal fraction / log2-exponent histogram of the grads
    (pre- and post-clip, and of the packed ZeRO-1 bucket payloads under its
    ``buckets`` phase), accumulated into ``opt_state["tensorstats"]``
    (which ``init_opt_state(..., tensorstats=cfg)`` must have created) and
    reported under ``metrics["tensorstats"]``.  The pre-clip rms reuses the
    grouped squared sums that already derive the clipping norm.  A pure
    observer: the update itself is bitwise-unchanged."""
    policy = policy or DtypePolicy()
    tstats = (tensorstats_cfg
              if tensorstats_cfg is not None
              and getattr(tensorstats_cfg, "enabled", False) else None)
    if tstats is not None and grad_group_fn is None:
        from neuronx_distributed_training_tpu.telemetry.health import (
            grad_group_of,
        )

        grad_group_fn = grad_group_of
    step = opt_state["step"] + 1
    # "clip" and "adamw" are inner scopes of the caller's "optimizer"
    # (telemetry.spans.DEVICE_SCOPES)
    with jax.named_scope("clip"):
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        if trainable_mask is not None:
            grads = jax.tree_util.tree_map(lambda g, m: g * m, grads, trainable_mask)
        group_sq = None
        if grad_group_fn is not None:
            group_sq = grouped_sq_norms(grads, grad_group_fn)
            total = None
            for s in group_sq.values():
                total = s if total is None else total + s
            gnorm = jnp.sqrt(total if total is not None else jnp.zeros((), jnp.float32))
        else:
            gnorm = global_norm(grads)
        track_finite = skip_nonfinite or grad_group_fn is not None \
            or extra_finite is not None
        updates_finite = None
        if track_finite:
            # any non-finite grad leaf poisons the squared-sum chain, so one
            # isfinite on the global norm covers the whole grad tree
            updates_finite = jnp.isfinite(gnorm)
            if extra_finite is not None:
                updates_finite = jnp.logical_and(
                    updates_finite, jnp.asarray(extra_finite, bool))
        grads_preclip = grads  # tensorstats pre-clip view (a reference, no copy)
        if cfg.grad_clip_norm is not None and cfg.grad_clip_norm > 0:
            clip = jnp.minimum(1.0, cfg.grad_clip_norm / (gnorm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * clip, grads)

    b1, b2 = cfg.beta1, cfg.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    masks = decay_mask(params, cfg)
    master = opt_state.get("master", params)
    lr = jnp.asarray(lr, jnp.float32)

    if trainable_mask is not None:
        # frozen params get no weight decay either
        masks = jax.tree_util.tree_map(lambda w, t: w * t, masks, trainable_mask)

    def mu_fn(mu, g):
        return b1 * mu.astype(jnp.float32) + (1 - b1) * g

    def nu_fn(nu, g):
        return b2 * nu.astype(jnp.float32) + (1 - b2) * jnp.square(g)

    def upd(m, mu, nu, wd_mask):
        mf = m.astype(jnp.float32)
        update = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
        update = update + cfg.weight_decay * wd_mask * mf
        return mf - lr * update

    packed_payloads = (
        {} if (tstats is not None and tstats.buckets
               and bucket_plan is not None and bucket_plan.buckets) else None)
    odt = policy.optimizer_dtype
    with jax.named_scope("adamw"):
        if bucket_plan is not None and bucket_plan.buckets:
            from neuronx_distributed_training_tpu.optim.overlap import (
                bucketed_update,
            )

            new_mu, new_nu, new_master, new_params = bucketed_update(
                bucket_plan, params, grads, opt_state["mu"], opt_state["nu"],
                master, masks, mu_fn=mu_fn, nu_fn=nu_fn, upd_fn=upd,
                prefetch=prefetch_ag, collect_packed=packed_payloads,
            )
        else:
            new_mu = jax.tree_util.tree_map(mu_fn, opt_state["mu"], grads)
            new_nu = jax.tree_util.tree_map(nu_fn, opt_state["nu"], grads)
            new_master = jax.tree_util.tree_map(upd, master, new_mu, new_nu, masks)
            new_params = jax.tree_util.tree_map(
                lambda x, p: x.astype(p.dtype), new_master, params
            )

        new_state = {
            "step": step,
            "mu": jax.tree_util.tree_map(lambda x: x.astype(odt), new_mu),
            "nu": jax.tree_util.tree_map(lambda x: x.astype(odt), new_nu),
        }
        if "master" in opt_state:
            new_state["master"] = jax.tree_util.tree_map(
                lambda x: x.astype(odt), new_master)
    if "ema" in opt_state:
        e = ema_cfg or EMAConfig()
        apply = jnp.logical_and(
            step >= e.start_step,
            jnp.remainder(step, e.apply_every_n_steps) == 0,
        )
        d = jnp.where(apply, e.decay, 1.0)
        new_state["ema"] = jax.tree_util.tree_map(
            lambda old, p: (d * old.astype(jnp.float32)
                            + (1.0 - d) * p.astype(jnp.float32)).astype(odt),
            opt_state["ema"], new_master,
        )
    ts_metrics = None
    if tstats is not None:
        from neuronx_distributed_training_tpu.telemetry.tensorstats import (
            tensorstats_update,
        )

        new_state["tensorstats"], ts_metrics = tensorstats_update(
            opt_state["tensorstats"], tstats, group_fn=grad_group_fn,
            grads_pre=grads_preclip, grads_post=grads, group_sq=group_sq,
            packed=packed_payloads,
        )
    if skip_nonfinite:
        # in-graph skip: a select per leaf keeps params/moments/master/EMA AND
        # the step counter (bias correction must not advance on a skipped
        # step) bitwise-identical to the incoming state when non-finite
        keep = lambda new, old: jnp.where(updates_finite, new, old)
        new_params = jax.tree_util.tree_map(keep, new_params, params)
        new_state = {
            k: jax.tree_util.tree_map(keep, v, opt_state[k])
            for k, v in new_state.items()
        }
    metrics = {"grad_norm": gnorm}
    if updates_finite is not None:
        metrics["updates_finite"] = updates_finite
    if group_sq is not None:
        metrics["group_norms"] = {k: jnp.sqrt(v) for k, v in group_sq.items()}
    if ts_metrics is not None:
        metrics["tensorstats"] = ts_metrics
    return new_params, new_state, metrics


# ---------------------------------------------------------------------------
# ZeRO-1 sharding specs
# ---------------------------------------------------------------------------


def zero1_leaf_spec(spec: P, shape, mesh: Mesh, dp_axes=("data", "expert")) -> P:
    """Extend a param spec with DP sharding on the first unsharded, divisible dim.

    This is ZeRO-1: optimizer moments/master weights sharded over the DP group.
    Axes the param spec already uses (e.g. ``expert`` on MoE weights) are
    skipped — a mesh axis may appear at most once per spec.  Falls back to the
    param spec (replicated over DP) when nothing divides.
    """
    used = {
        a
        for e in spec
        if e is not None
        for a in (e if isinstance(e, tuple) else (e,))
    }
    avail = tuple(
        a for a in dp_axes if int(mesh.shape.get(a, 1)) > 1 and a not in used
    )
    dp_total = 1
    for a in avail:
        dp_total *= int(mesh.shape.get(a, 1))
    if dp_total == 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % dp_total == 0:
            entries[i] = avail if len(avail) > 1 else avail[0]
            return P(*entries)
    return spec


def opt_state_specs(params, param_specs, mesh: Mesh, *, zero1: bool = True,
                    policy: DtypePolicy | None = None,
                    zero1_exclude: tuple = (), ema: bool = False,
                    health: bool = False, tensorstats=None,
                    tensorstats_bucket_groups: tuple = ()):
    """Spec pytree matching ``init_opt_state`` output.

    ``zero1_exclude`` names path substrings whose moments keep the plain param
    spec (no DP sharding) — a generic escape hatch; nothing in the stock
    models needs it (the former embedding-under-PP exclusion was removed by
    switching the pipeline embed hooks to the one-hot matmul form, see
    ``ops.linear.apply_embedding``)."""
    policy = policy or DtypePolicy()

    if zero1:
        shapes = jax.tree_util.tree_map(lambda x: x.shape, params)

        def leaf_spec(path, s, sh):
            p = _path_str(path)
            if any(x in p for x in zero1_exclude):
                return s
            return zero1_leaf_spec(s, sh, mesh)

        moment_specs = jax.tree_util.tree_map_with_path(
            leaf_spec,
            param_specs,
            shapes,
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        moment_specs = param_specs
    out = {"step": P(), "mu": moment_specs, "nu": moment_specs}
    if jnp.dtype(policy.param_dtype) != jnp.dtype(policy.optimizer_dtype):
        out["master"] = moment_specs
    if ema:
        out["ema"] = moment_specs
    if health:
        out["health"] = {k: P() for k in HEALTH_STATE_KEYS}
    if tensorstats is not None and getattr(tensorstats, "enabled", False):
        from neuronx_distributed_training_tpu.telemetry.tensorstats import (
            tensorstats_state_specs,
        )

        out["tensorstats"] = tensorstats_state_specs(
            tensorstats, params,
            bucket_groups=tuple(tensorstats_bucket_groups))
    return out
