"""The jitted training step.

Replaces the reference's ``BaseModelModule.training_step`` /
``forward_backward_step`` (``base.py:180-395``): zero-grad + microbatch loop +
``loss.backward()`` accumulation + optimizer step + loss reductions become ONE
compiled function:

- microbatch gradient accumulation is a ``lax.scan`` over a leading microbatch
  dim, accumulating in ``grad_accum_dtype`` (the reference's
  ``loss/num_microbatches`` scaling at ``base.py:364-373`` and fp32-grad-acc
  option at ``base.py:128-132``);
- the DP/CP loss all-reduces (``base.py:387-395``) are implicit — the loss is a
  global masked mean over a sharded batch, so GSPMD inserts them;
- the ZeRO-1 optimizer update runs on DP-sharded optimizer state
  (``optim/adamw.py``) with grad-norm clipping inside, exactly where the
  reference's wrapped optimizer does it (``nlp_overrides.py:203-216``).

There is no ``xm.mark_step`` anywhere: the jit boundary is the graph boundary.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, adamw_update, global_norm
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import DATA_AXES
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

# loss_fn(params, batch, step_key) -> (loss, aux_dict)
LossFn = Callable[[Any, dict[str, jax.Array], jax.Array], tuple]


def microbatch_split(batch: dict[str, jax.Array], num_microbatches: int):
    """[gbs, ...] -> [num_micro, gbs/num_micro, ...] (the get_batch_iterator
    analogue, reference ``base.py:330-350``)."""
    def split(x):
        return x.reshape((num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def make_train_step(
    loss_fn: LossFn,
    opt_cfg: AdamWConfig,
    lr_schedule: Callable,
    policy: DtypePolicy,
    *,
    num_microbatches: int = 1,
    log_param_norm: bool = False,
    log_gradient_norm: bool = False,
    trainable_mask: Any = None,  # peft.lora.trainable_mask for LoRA freeze
    ema_cfg: Any = None,  # optim.adamw.EMAConfig; state must carry an "ema" tree
    param_specs: Any = None,  # pin grads to the param sharding (see below)
    loss_and_grad_fn: Optional[Callable] = None,  # manual-grad schedules (1F1B)
    health_cfg: Any = None,  # telemetry.health.HealthConfig (numerics probes)
    bucket_plan: Any = None,  # optim.overlap.BucketPlan (engineered overlap)
    prefetch_ag: bool = True,
    tensorstats_cfg: Any = None,  # telemetry.tensorstats.TensorStatsConfig
    after_update: Any = None,  # models.family.AfterUpdate (a rule beside the optimizer's)
) -> Callable:
    """Build the (un-jitted) train step:
    ``(params, opt_state, batch, step_key) -> (params, opt_state, metrics)``.

    ``loss_and_grad_fn`` — ``(params, batch, step_key) -> (loss, aux, grads)``
    — replaces the ``jax.value_and_grad`` of ``loss_fn`` when a schedule
    computes its own gradients (the manual-vjp 1F1B pipeline).  Everything
    downstream of the gradients — grad-accum dtype, the param-sharding pin,
    the AdamW/ZeRO-1 update, metrics — is the SAME code path, so the
    optimizer boundary is schedule-independent.

    ``health_cfg`` (enabled): the numerics flight recorder's in-graph probes —
    per-layer-group grad norms (sharing the clipping norm's reduction pass),
    loss finiteness, an ``updates_finite`` flag, cumulative anomaly counters
    threaded through ``opt_state["health"]`` (which ``init_opt_state(...,
    health=True)`` must have created), and — under ``policy: skip_update`` —
    the in-graph suppression of a non-finite update.  All of it rides the one
    jitted executable; the host sees the results only at the boundary metric
    fetch it already performs.

    ``tensorstats_cfg`` (enabled): the tensor numerics observatory
    (``telemetry.tensorstats``) — per layer-group dynamic-range stats of the
    optimizer-boundary grads, cumulated in ``opt_state["tensorstats"]`` and
    surfaced as ``tensorstats/...`` scalars plus ``tensorstats_hist/...``
    packed vectors in the boundary metrics.  Shares the health probes' layer
    grouping and the clipping norm's reduction pass; rides the same one
    executable.

    ``after_update``: the family's leaves that move by a rule of their own
    (``models.family.AfterUpdate``).  The entries of the loss's aux that the
    rule reads pass the scalar filter whole, are summed over the micro-batches
    (every other entry is averaged), and after the optimizer's update, inside
    this step, ``apply`` returns the parameters with those leaves moved; where
    ``health.policy: skip_update`` suppresses a non-finite step, the rule's
    move is suppressed with it."""
    kept = tuple(after_update.reads) if after_update is not None else ()
    health = health_cfg if (health_cfg is not None
                            and getattr(health_cfg, "enabled", False)) else None
    tstats = (tensorstats_cfg
              if tensorstats_cfg is not None
              and getattr(tensorstats_cfg, "enabled", False) else None)
    if health is not None or tstats is not None:
        from neuronx_distributed_training_tpu.telemetry.health import (
            grad_group_of,
        )

    def grad_one_microbatch(params, mb, step_key):
        def scalar_loss(p):
            loss, aux = loss_fn(p, mb, step_key)
            # scalar aux entries (DPO rewards, ORPO odds, MoE router loss)
            # surface as logged metrics — the reference's misc_metrics flow
            # (base_dpo.py:104-109); non-scalars (logits) stay internal
            scalars = {
                k: jnp.asarray(v, jnp.float32)
                for k, v in aux.items()
                if jnp.ndim(v) == 0 or k in kept
            }
            return loss.astype(jnp.float32), scalars

        return jax.value_and_grad(scalar_loss, has_aux=True)(params)

    def train_step(params, opt_state, batch, step_key):
        if loss_and_grad_fn is not None:
            loss, aux, grads = loss_and_grad_fn(params, batch, step_key)
            loss = loss.astype(jnp.float32)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(policy.grad_accum_dtype), grads
            )
        elif num_microbatches == 1:
            (loss, aux), grads = grad_one_microbatch(params, batch, step_key)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(policy.grad_accum_dtype), grads
            )
        else:
            mbs = microbatch_split(batch, num_microbatches)

            def body(carry, mb):
                loss_sum, grad_sum = carry
                (loss, aux), grads = grad_one_microbatch(params, mb, step_key)
                with jax.named_scope("grad_accum"):
                    grad_sum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(policy.grad_accum_dtype),
                        grad_sum, grads
                    )
                    if param_specs is not None:
                        # Pin the accumulation carry to the param sharding,
                        # not just the post-loop grads (line ~161): the
                        # carry's layout is otherwise re-solved from its
                        # consumers, and extra read-only uses of the grads
                        # (the tensorstats reductions) can tip the
                        # partitioner into a different carry sharding that
                        # reshards the embedding-backward scatter-add INSIDE
                        # the loop on every microbatch
                        grad_sum = jax.tree_util.tree_map(
                            lambda s, g: shd.constrain(g, s), param_specs,
                            grad_sum, is_leaf=lambda x: isinstance(x, P),
                        )
                return (loss_sum + loss, grad_sum), aux

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, policy.grad_accum_dtype), params
            )
            (loss_sum, grad_sum), aux_stack = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), mbs
            )
            inv = 1.0 / num_microbatches
            loss = loss_sum * inv
            with jax.named_scope("grad_accum"):
                grads = jax.tree_util.tree_map(lambda g: g * inv, grad_sum)
            aux = {k: jnp.sum(v, axis=0) if k in kept else jnp.mean(v)
                   for k, v in aux_stack.items()}

        if param_specs is not None:
            # Pin gradients to the PARAM sharding at the loss->optimizer
            # boundary.  ZeRO-1 moments can be sharded on a dim the param
            # spec leaves free (e.g. the embed table's hidden dim over
            # ``data`` when vocab is taken by ``model``); without this pin
            # the partitioner back-propagates that layout into the
            # activation-cotangent chain — observed as an "involuntary full
            # rematerialization" on the pp x cp mesh — instead of resharding
            # the small [vocab, h] grad right here.
            grads = jax.tree_util.tree_map(
                lambda s, g: shd.constrain(g, s), param_specs, grads,
                is_leaf=lambda x: isinstance(x, P),
            )

        lr = lr_schedule(opt_state["step"])
        # scope names: telemetry.spans.DEVICE_SCOPES
        with jax.named_scope("optimizer"):
            new_params, new_opt_state, opt_metrics = adamw_update(
                params, grads, opt_state, lr, opt_cfg, policy,
                trainable_mask=trainable_mask, ema_cfg=ema_cfg,
                grad_group_fn=(grad_group_of
                               if (health is not None or tstats is not None)
                               else None),
                skip_nonfinite=(health is not None
                                and health.policy == "skip_update"),
                extra_finite=(jnp.isfinite(loss) if health is not None
                              else None),
                bucket_plan=bucket_plan, prefetch_ag=prefetch_ag,
                tensorstats_cfg=tstats,
            )
            if after_update is not None:
                moved = after_update.apply(new_params, {k: aux.pop(k) for k in kept})
                if health is not None and health.policy == "skip_update":
                    # a suppressed step moves nothing: the rule's leaves stay
                    # too (their counts may come off a non-finite forward)
                    ok = opt_metrics["updates_finite"]
                    moved = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), moved, new_params)
                new_params = moved
        metrics = {
            "loss": loss,
            "lr": jnp.asarray(lr, jnp.float32),
            "grad_norm": opt_metrics["grad_norm"],
        }
        metrics.update({k: v for k, v in aux.items() if k not in metrics})
        if tstats is not None:
            # tensorstats/... per-step scalars + tensorstats_hist/... packed
            # cumulative vectors — the loop's boundary fetch splits them by
            # prefix (floats to the scalar sinks, vectors to tensorstats.jsonl)
            metrics.update(opt_metrics.get("tensorstats", {}))
        if health is not None:
            ok = opt_metrics["updates_finite"]
            bad = jnp.logical_not(ok).astype(jnp.int32)
            prev = opt_state["health"]
            # steps_seen counts train-step INVOCATIONS (unlike opt step, which
            # freezes on a skipped update) — steps_seen - 1 is the 0-based
            # trainer step just computed, the id the forensic bundle names
            seen = prev["steps_seen"] + 1
            hstate = {
                "steps_seen": seen,
                "nonfinite_count": prev["nonfinite_count"] + bad,
                "skipped_count": prev["skipped_count"] + (
                    bad if health.policy == "skip_update"
                    else jnp.zeros((), jnp.int32)),
                "last_nonfinite_step": jnp.where(
                    bad == 1, seen - 1, prev["last_nonfinite_step"]),
            }
            new_opt_state["health"] = hstate
            metrics["health/updates_finite"] = ok.astype(jnp.float32)
            metrics["health/loss_finite"] = jnp.isfinite(loss).astype(
                jnp.float32)
            metrics["health/nonfinite_count"] = hstate["nonfinite_count"]
            metrics["health/skipped_count"] = hstate["skipped_count"]
            metrics["health/last_nonfinite_step"] = (
                hstate["last_nonfinite_step"])
            for g, n in opt_metrics.get("group_norms", {}).items():
                metrics[f"health/grad_norm/{g}"] = n
            if health.param_norm:
                # post-update param norm: the host-side monitor diffs ring
                # entries to surface drift (a slow divergence the per-step
                # grad norm alone doesn't show)
                metrics["health/param_norm"] = global_norm(new_params)
        if log_param_norm:
            # reference log_parameter_norm (base.py:397-452): TP/CP/PP-group
            # all-reduced norm — here a plain global norm (params are one
            # global pytree under GSPMD).
            metrics["param_norm"] = global_norm(new_params)
        if log_gradient_norm:
            # reference log_gradient_norm (base.py:397-452): the pre-clip
            # grad norm under the reference's metric name (grad_norm is
            # always logged; this adds the explicit parity alias)
            metrics["gradient_norm"] = opt_metrics["grad_norm"]
        return new_params, new_opt_state, metrics

    return train_step


def make_eval_step(loss_fn: LossFn) -> Callable:
    def eval_step(params, batch, step_key=None):
        # key=None signals eval mode: models with dropout (GPT) must run
        # deterministically during validation
        loss, _aux = loss_fn(params, batch, None)
        return {"val_loss": loss.astype(jnp.float32)}

    return eval_step


def jit_train_step(
    train_step: Callable,
    mesh: Mesh,
    param_specs,
    opt_specs,
    *,
    batch_spec: Optional[P] = None,
    donate: bool | str = True,
):
    """jit with explicit in/out shardings; params/opt-state donated (in-place
    buffer reuse — the memory behavior the reference gets from in-place
    ``optimizer.step``).

    ``donate``: True/"all" donates params + opt state; "params" donates the
    params tree only (the narrowed EMA workaround — see Trainer.from_config);
    False/"none" disables donation."""
    if batch_spec is None:
        batch_spec = P(DATA_AXES)
    ns = functools.partial(NamedSharding, mesh)
    p_sh = jax.tree_util.tree_map(ns, param_specs, is_leaf=lambda x: isinstance(x, P))
    o_sh = jax.tree_util.tree_map(ns, opt_specs, is_leaf=lambda x: isinstance(x, P))
    donate_argnums = {
        True: (0, 1), "all": (0, 1), "params": (0,), False: (), "none": (),
    }[donate]
    return jax.jit(
        train_step,
        in_shardings=(p_sh, o_sh, ns(batch_spec), None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=donate_argnums,
    )
