"""Pipeline parallelism over the ``pipe`` mesh axis.

TPU-native replacement for NxD's pipeline engine (``NxDPPModel.run_train`` —
reference ``base.py:374-383`` — with its FX tracer/auto-partitioner and 1F1B
P2P schedule, configured by ``pipeline_config`` at ``base.py:136-157``).
Re-designed rather than translated:

- **no tracer**: models here are stacked layer pytrees; "partitioning" is just
  sharding the leading ``[num_layers, ...]`` dim over ``pipe``
  (``auto_partition`` with equal cuts falls out; manual ``pipeline_cuts`` are
  unnecessary when stages are equal-sized by construction);
- **schedule**: microbatches stream through stages inside one jitted
  ``lax.scan``; stage outputs move over ICI with ``lax.ppermute``.  Forward is
  the classic GPipe wavefront (num_micro + pp - 1 ticks); **backward is
  derived by autodiff** — ``scan``/``ppermute`` transpose to the reverse
  wavefront, giving a full fwd-then-bwd schedule.  Per-stage activations are
  rematerialized (``jax.checkpoint``) so only stage *inputs* are saved, the
  same memory class as the reference's 1F1B-with-recompute;
- **loss OUTSIDE the wavefront, balanced over ranks** (vs the reference's
  last-stage-only loss, ``base.py:378-381``): each completed microbatch's
  last-stage output is routed in one tick-uniform ppermute hop to rank
  ``m % pp`` and parked there; the lm-head + CE then run ONCE, outside the
  manual region, with the microbatch dim sharded over ``pipe``.  Total head FLOPs equal the unpipelined step (no per-rank
  redundancy, no warmup/cooldown ticks), and the head's wall-clock is
  ``nm/pp`` per rank instead of the reference's ``nm``-serial on the last
  stage.  (A per-rank ``lax.cond`` gate is NOT an option: GSPMD inserts
  collective-permutes inside the hooks whose rendezvous needs every device,
  so a pipe-divergent branch deadlocks — verified on the 8-device mesh.)
- **embedding also outside the wavefront**: all microbatch embeddings are
  computed once under plain GSPMD (pipe-sharded round-robin, gather path —
  the partitioner's gather-transpose crash only bites inside the manual
  submesh) and routed to rank 0 tick-by-tick with a tick-uniform
  switch+ppermute.  Net effect (tools/pp_flops_probe.py): pp=4 compiled
  FLOPs within 2.1% of the unpipelined step at equal tokens — the residual
  is bubble-tick stage compute, which costs no wall-clock;
- embedding/head weights live OUTSIDE the pipelined stack and are replicated
  over ``pipe`` (they are still TP-sharded over ``model`` by GSPMD's auto
  axes) — a deliberate departure from the reference's stage-0/stage-N
  placement + embedding-tying all-reduce protocol (``module.py:28-157``).

``shard_map`` is manual over ``pipe`` only (``axis_names={"pipe"}``): data/
tensor/sequence sharding inside the body remains GSPMD-driven, so the same
model code runs under any tp x dp combination.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_training_tpu.parallel import sharding as shd

PIPE_AXIS = "pipe"

# EmbedFn:    (params, microbatch_dict) -> activations [mb, s, h]
# StageFn:    (local_layer_params, activations, microbatch_dict) -> activations,
#             or (activations, aux_scalar) when ``stage_aux=True`` (the MoE
#             router-loss carry: each stage contributes its local layers' aux)
# LossFn:     (params, activations, microbatch_dict) -> (scalar loss, scalar denom)
# The microbatch dict passed to StageFn additionally carries ``_chunk`` (the
# virtual-pipeline chunk index, 0 when vp == 1) so stages can derive
# stage-unique PRNG keys for dropout.
EmbedFn = Callable[[Any, dict], jax.Array]
StageFn = Callable[[Any, jax.Array, dict], jax.Array]
LossFn = Callable[[Any, jax.Array, dict], tuple]


def stage_layer_slice(num_layers: int, pp: int, vp: int = 1) -> int:
    if num_layers % (pp * vp) != 0:
        raise ValueError(
            f"num_layers {num_layers} not divisible by pp*vp = {pp}*{vp}"
        )
    return num_layers // (pp * vp)


def to_interleaved(layer_stack: Any, pp: int, vp: int) -> Any:
    """[L, ...] stacked layers -> [vp, pp, Lc, ...] stage-major layout.

    Stage ``s = c*pp + r`` (chunk c on rank r) covers layers
    ``[s*Lc, (s+1)*Lc)`` — the interleaved assignment of the reference's
    ``virtual_pipeline_model_parallel_size`` (``base.py:85,155``).  Pure
    reshape: layer index ``l = (c*pp + r)*Lc + k`` has dims ordered (c, r, k),
    so the ``pp`` dim can be sharded over ``pipe`` without any transpose.
    """

    def one(x):
        L = x.shape[0]
        lc = stage_layer_slice(L, pp, vp)
        return x.reshape((vp, pp, lc) + x.shape[1:])

    return jax.tree_util.tree_map(one, layer_stack)


def from_interleaved(layer_stack: Any) -> Any:
    """Inverse of ``to_interleaved``: [vp, pp, Lc, ...] -> [L, ...]."""

    def one(x):
        vp, pp, lc = x.shape[:3]
        return x.reshape((vp * pp * lc,) + x.shape[3:])

    return jax.tree_util.tree_map(one, layer_stack)


def pipeline_loss(
    params: Any,
    layer_params: Any,  # vp==1: [num_layers, ...] dim0 over "pipe";
                        # vp>1: interleaved [vp, pp, Lc, ...] dim1 over "pipe"
    microbatches: dict[str, jax.Array],  # leaves [num_micro, mb, ...]
    *,
    embed_fn: EmbedFn,
    stage_fn: StageFn,
    loss_fn: LossFn,
    mesh=None,
    num_microbatches: Optional[int] = None,
    virtual_pipeline_size: int = 1,
    stage_aux: bool = False,
    aux_scale: float = 0.0,
) -> jax.Array:
    """Scalar pipeline-parallel loss (mean over microbatches).

    ``virtual_pipeline_size > 1`` runs the interleaved/circular schedule
    (reference VPP, ``base.py:85,155``): each rank holds ``vp`` non-adjacent
    layer chunks (pass ``to_interleaved(layers, pp, vp)``), microbatches cycle
    through the ranks ``vp`` times, and per-rank utilization improves from
    ``nm/(nm+pp-1)`` to ``nm*vp/(nm*vp+pp-1)``.

    Falls back to a plain sequential microbatch loop when pp == 1, so the same
    entry point drives both pipelined and unpipelined configs.
    """
    mesh = mesh or shd.active_mesh()
    pp = int(mesh.shape.get(PIPE_AXIS, 1)) if mesh is not None else 1
    nm = num_microbatches or jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    vp = virtual_pipeline_size
    if vp > 1 and 1 < pp and nm < pp:
        # chunk c+1 reads the circular store at tick c*nm + m, but the last
        # rank's chunk-c output is only parked at tick c*nm + m + pp — with
        # nm < pp the read precedes the write and the loss is silently wrong
        raise ValueError(
            f"interleaved pipeline needs num_microbatches >= pp "
            f"(got nm={nm}, pp={pp}, vp={vp})"
        )

    if pp == 1:
        if vp > 1:
            layer_params = from_interleaved(layer_params)
        # same remat class as the pp>1 wavefront: per microbatch only the
        # stage input is saved (without this, the scan retains every layer's
        # activations for all nm microbatches)
        stage_ck = jax.checkpoint(stage_fn)

        def body(acc, mb):
            x = embed_fn(params, mb)
            out = stage_ck(layer_params, x, {**mb, "_chunk": jnp.zeros((), jnp.int32)})
            x, s_aux = out if stage_aux else (out, jnp.zeros((), jnp.float32))
            loss, denom = loss_fn(params, x, mb)
            return (acc[0] + loss, acc[1] + denom, acc[2] + s_aux), None

        (loss_sum, denom_sum, aux_sum), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
             jnp.zeros((), jnp.float32)),
            microbatches,
        )
        return loss_sum / jnp.maximum(denom_sum, 1.0) + aux_scale * aux_sum

    from jax.sharding import PartitionSpec as P

    # round-robin layout shared by the embed feed and the loss parking:
    # row g = r*slots + l <-> microbatch m = l*pp + r, dim 0 sharded over pipe
    slots = -(-nm // pp)
    g = np.arange(pp * slots)
    m_of_g = (g % slots) * pp + g // slots
    real = m_of_g < nm
    m_idx = np.where(real, m_of_g, 0)
    mb_perm = jax.tree_util.tree_map(lambda x: x[m_idx], microbatches)

    # ---- embedding, once, outside the manual region --------------------
    # Per-device FLOPs = (nm/pp) embeds (vs every-rank-every-tick inside the
    # wavefront), and the hook may use the plain gather path — the SPMD
    # partitioner's gather-transpose CHECK-crash only bites inside the manual
    # pipe submesh.  Rank m % pp holds microbatch m's embedding; the body
    # routes it to rank 0 at tick m with a tick-uniform switch + ppermute.
    emb = jax.vmap(lambda m: embed_fn(params, m))(mb_perm)
    # constrain ONLY the leading (pipe) dim: the trailing dims keep the
    # hook's own sharding (batch over data, seq over model under SP) — a bare
    # P("pipe") would pin them replicated and all-gather the whole global
    # batch's embeddings across data
    unc = P.UNCONSTRAINED
    emb = shd.constrain(emb, P(PIPE_AXIS, *([unc] * (emb.ndim - 1))))

    body = functools.partial(
        _pipeline_body,
        stage_fn=stage_fn, pp=pp, nm=nm, vp=vp, slots=slots,
        stage_aux=stage_aux,
    )
    layer_spec = P(None, PIPE_AXIS) if vp > 1 else P(PIPE_AXIS)
    fn = shd.shard_map(
        body,
        mesh=mesh,
        # manual over pipe only: layers sharded on their pipe dim,
        # microbatches replicated across pipe (GSPMD still shards them over
        # data/model inside); the embed feed and the parked outputs are
        # pipe-sharded on dim 0.  (params themselves are not an operand —
        # the embed and loss hooks, the only consumers, run outside.)
        in_specs=(layer_spec, P(), P(PIPE_AXIS)),
        out_specs=(P(PIPE_AXIS), P()),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )
    parked, aux_total = fn(layer_params, microbatches, emb)

    # ---- head + CE, once, outside the manual region --------------------
    # parked row g holds microbatch m_of_g's last-stage output (same layout
    # as the embed feed), sharded over pipe — the loss below is pipe-parallel.

    def resh(x):  # [pp*slots, ...] -> [slots, pp, ...]; pp dim stays sharded
        return jnp.swapaxes(x.reshape((pp, slots) + x.shape[1:]), 0, 1)

    y_r = resh(parked)
    mb_r = jax.tree_util.tree_map(resh, mb_perm)
    mask_r = jnp.swapaxes(
        jnp.asarray(real, jnp.float32).reshape(pp, slots), 0, 1
    )
    # remat: per scan step only (y_i, mb_i) are saved; head/CE intermediates
    # (the [*, s, vocab]-class buffers) are recomputed in backward
    vloss = jax.checkpoint(
        jax.vmap(lambda y, mb: loss_fn(params, y, mb), in_axes=(0, 0))
    )

    def lbody(acc, xs):
        y_i, mb_i, mk = xs
        l_v, d_v = vloss(y_i, mb_i)
        return (acc[0] + jnp.sum(l_v * mk), acc[1] + jnp.sum(d_v * mk)), None

    (loss_sum, denom_sum), _ = jax.lax.scan(
        lbody,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (y_r, mb_r, mask_r),
    )
    return loss_sum / jnp.maximum(denom_sum, 1.0) + aux_scale * aux_total


def _pipeline_body(local_layers, microbatches, emb, *, stage_fn,
                   pp, nm, vp, slots, stage_aux=False):
    """Per-pipe-rank circular wavefront loop (inside shard_map, manual "pipe").

    Schedule: rank ``r`` at tick ``t`` works on work-index ``w = t - r`` —
    microbatch ``m = w mod nm`` of chunk ``c = w // nm``.  Chunk hand-off
    between chunks rides a per-microbatch circular store on rank 0 (outputs of
    the last rank come back around the cyclic ring one tick later and wait in
    ``circ_storage`` until chunk ``c+1``'s slot).  Total ticks
    ``nm*vp + pp - 1``.  With vp == 1 this is the plain GPipe wavefront.

    ``emb [slots, mb, s, h]`` is this rank's round-robin share of the
    pre-computed microbatch embeddings (microbatch ``m`` lives on rank
    ``m % pp`` at slot ``m // pp``); the body routes slot ``t // pp`` from
    rank ``t % pp`` to rank 0 at tick ``t`` — both the branch index and the
    ``t < nm`` gate depend only on the tick, so every device takes the same
    path and the collective-permute inside is safe (a RANK-dependent gate
    would deadlock: GSPMD collectives need every device at the rendezvous).

    Returns ``(parked, aux)``: ``parked [slots, mb, s, h]`` holds the
    final-chunk outputs of the microbatches this rank parks (same layout as
    ``emb``) — the caller computes the loss over them outside the manual
    region — and ``aux`` is the MoE router-aux total, psum-closed over pipe.
    """
    rank = jax.lax.axis_index(PIPE_AXIS)
    is_first = rank == 0
    is_last = rank == pp - 1

    # normalize local layer layout to [vp, Lc, ...]
    if vp > 1:
        local_layers = jax.tree_util.tree_map(
            lambda x: jnp.squeeze(x, axis=1), local_layers
        )
    else:
        local_layers = jax.tree_util.tree_map(lambda x: x[None], local_layers)

    x0 = emb[0]  # shape/dtype template for the stream buffers

    # rematerialize stage activations in backward: only stage inputs are
    # saved — the stage-input O(nm * mbs*s*h) class, the same trade the
    # reference's 1F1B-with-recompute makes.  (The embed and loss hooks left
    # the tick loop entirely — see pipeline_loss.)  The per-chunk layer
    # slicing happens INSIDE the checkpointed region: sliced with a traced
    # chunk index OUTSIDE it, the slice becomes a per-tick residual the scan
    # stacks — a params-sized save every tick (measured 0.5 GiB x L x nm at
    # 70B shape, tools/pp_memory_flagship.py) instead of one loop-invariant
    # reference to the param buffer.
    def _stage_sliced(ll, c, x, mb):
        lp_c = jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            ll,
        )
        return stage_fn(lp_c, x, mb)

    compute = jax.checkpoint(_stage_sliced)

    cyclic = [(i, (i + 1) % pp) for i in range(pp)]

    def tick(carry, t):
        recv, circ, park, aux_acc = carry

        if vp > 1:
            # rank 0: recv holds last-rank output from tick t-1 (work index
            # w_back); park it in the circular store for its next chunk
            w_back = t - 1 - (pp - 1)
            m_back = jnp.clip(jnp.remainder(w_back, nm), 0, nm - 1)
            back_valid = jnp.logical_and(w_back >= 0, w_back < nm * (vp - 1))
            slot = jax.lax.dynamic_index_in_dim(circ, m_back, 0, keepdims=False)
            circ = jax.lax.dynamic_update_index_in_dim(
                circ, jnp.where(back_valid, recv, slot), m_back, 0
            )

        w = t - rank
        w_c = jnp.clip(w, 0, nm * vp - 1)
        m = jnp.remainder(w_c, nm)
        c = w_c // nm
        mb = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, m, 0, keepdims=False),
            microbatches,
        )
        # rank 0 consumes microbatch t's embedding at tick t (< nm): fetch it
        # from its round-robin owner.  Branch index and gate are tick-only —
        # uniform across every device (see docstring).
        e_t = jax.lax.dynamic_index_in_dim(
            emb, jnp.clip(t // pp, 0, slots - 1), 0, keepdims=False
        )
        fresh = jax.lax.cond(
            t < nm,
            lambda: jax.lax.switch(
                jnp.remainder(t, pp),
                [functools.partial(
                    jax.lax.ppermute, e_t, PIPE_AXIS, [(o, 0)]
                ) for o in range(pp)],
            ),
            lambda: jnp.zeros(x0.shape, x0.dtype),
        )
        if vp > 1:
            parked_in = jax.lax.dynamic_index_in_dim(circ, m, 0, keepdims=False)
            first_in = jnp.where(c == 0, fresh, parked_in)
        else:
            first_in = fresh
        x = jnp.where(is_first, first_in, recv)

        out = compute(local_layers, c, x, {**mb, "_chunk": c})
        y, s_aux = out if stage_aux else (out, jnp.zeros((), jnp.float32))
        # every rank+chunk contributes its local layers' aux once per valid
        # work index (the MoE router-loss carry: psum over pipe at the end
        # sums over ALL layers, exactly like the unpipelined scan carry)
        work_valid = jnp.logical_and(w >= 0, w < nm * vp)
        aux_acc = aux_acc + jnp.where(work_valid, s_aux, 0.0)

        # microbatch m_done finishes its LAST chunk on the last rank this
        # tick; route it to its parking rank m_done % pp in ONE hop (the
        # same tick-uniform switch + ppermute as the embed feed above — the
        # destination depends only on the tick, so every device takes the
        # same branch).  The loss is computed over the parked outputs
        # outside the manual region.
        w_done = t - (pp - 1)
        done_valid = jnp.logical_and(
            w_done >= nm * (vp - 1), w_done < nm * vp
        )
        m_done = jnp.clip(jnp.remainder(w_done, nm), 0, nm - 1)
        y_b = jax.lax.cond(
            done_valid,
            lambda: jax.lax.switch(
                jnp.remainder(m_done, pp),
                [functools.partial(
                    jax.lax.ppermute, y, PIPE_AXIS, [(pp - 1, o)]
                ) for o in range(pp)],
            ),
            lambda: jnp.zeros(x0.shape, x0.dtype),
        )
        mine = jnp.logical_and(done_valid, jnp.remainder(m_done, pp) == rank)
        p_slot = m_done // pp
        cur = jax.lax.dynamic_index_in_dim(park, p_slot, 0, keepdims=False)
        park = jax.lax.dynamic_update_index_in_dim(
            park, jnp.where(mine, y_b, cur), p_slot, 0
        )

        recv = jax.lax.ppermute(y, PIPE_AXIS, cyclic)
        return (recv, circ, park, aux_acc), None

    zeros = jnp.zeros_like(x0)
    circ0 = (
        jnp.zeros((nm,) + x0.shape, x0.dtype) if vp > 1 else jnp.zeros((1, 1), x0.dtype)
    )
    park0 = jnp.zeros((slots,) + x0.shape, x0.dtype)
    (_, _, park, aux_acc), _ = jax.lax.scan(
        tick,
        (zeros, circ0, park0, jnp.zeros((), jnp.float32)),
        jnp.arange(nm * vp + pp - 1),
    )
    return park, jax.lax.psum(aux_acc, PIPE_AXIS)


# ---------------------------------------------------------------------------
# 1F1B: single-pass schedule with in-loop pipe-sharded head and manual grads
# ---------------------------------------------------------------------------
#
# The GPipe-wavefront-with-autodiff above is transparent to ``jax.grad`` but
# pays for it in memory: autodiff of the tick scan retains one stage input per
# tick — O(nm + pp) activation-sized residuals per rank (measured 0.45 GiB/tick
# at flagship shape, bench_results/pp_memory_flagship.md).  The reference's
# engine instead runs 1F1B (``base.py:374-383``): backward for microbatch m
# starts as soon as its forward leaves the last stage, bounding in-flight
# activations to O(pp).
#
# ``pipeline_loss_and_grad`` is the TPU-native 1F1B: ONE ``lax.scan`` over a
# WORK-COMPACTED schedule table (``work_table`` below — schedule as data): at
# each compacted tick, rank ``r`` executes the table's (kind, microbatch,
# chunk) entry for that tick, with the forward / head / backward / wgrad
# blocks gated on tick-uniform ``lax.cond`` flags so a tick no rank forwards
# (backwards) on costs nothing.  Because JAX autodiff cannot interleave a
# scan's backward into its forward, the backward is MANUAL: each B tick calls
# ``jax.vjp`` on the stage (recompute-and-backprop within the tick — the same
# FLOPs as the wavefront's rematerialized backward), activation cotangents ride
# the reverse ring, and parameter gradients accumulate in the scan carry.
# Saved state is an interval-allocated ring of stage inputs — the O(pp) class.
#
# The lm-head + CE cannot stay hoisted (its cotangent would be needed before
# the forward scan ends), so it moves INSIDE the tick loop, sharded over
# ``pipe`` on the VOCAB dim: when microbatch m finishes at tick m + pp - 1 its
# output is broadcast over the pipe ring (one psum) and every rank computes
# logits for its V/pp vocab slice — total head FLOPs stay at parity with the
# unpipelined step (the property tests/test_pp_flops_parity.py pins), and the
# closed-form CE backward (softmax - onehot) yields dy in the same tick.
# This works because both backward seeds are known before the loss value:
# d(loss)/d(loss_sum) = 1/denom_total (denom is a function of labels only) and
# d(loss)/d(stage aux) = aux_scale.
#
# Scope: plain matmul head (tied embed or lm_head.w), token-level CE
# (pretrain/SFT).  Three manual-vjp variants share the tick loop:
# ``1f1b`` (vp == 1), ``1f1b-interleaved`` (vp > 1: the circular interleave
# above, backward threaded through the same chunk ring), and ``1f1b-zb``
# (vp == 1, ZB-H1-style: the backward tick splits into a dgrad pass whose
# activation cotangent feeds the upstream stage immediately and a wgrad pass
# deferred ``rank`` ticks into this rank's cooldown bubble).  Preference
# alignment and exotic heads keep the autodiff wavefront —
# ``supports_1f1b`` is the gate.


PIPELINE_SCHEDULES = ("auto", "1f1b", "1f1b-interleaved", "1f1b-zb",
                      "wavefront")
#: the manual-vjp family (everything but the autodiff wavefront)
MANUAL_VJP_SCHEDULES = ("1f1b", "1f1b-interleaved", "1f1b-zb")


def blocked_1f1b_reason(parallel_cfg: dict,
                        schedule: str = "1f1b") -> Optional[str]:
    """Config-SHAPE constraints on a manual-vjp schedule (no model object
    needed).

    The single source of truth shared by ``supports_1f1b`` (trainer build)
    and ``config.loader.validate_config`` (load time) — one wording, one
    catalog, whichever layer fires first.  Returns the blocking reason, or
    None when the shape qualifies (the model-family checks in
    ``supports_1f1b`` still apply).
    """
    pp = int(parallel_cfg.get("pipeline_model_parallel_size", 1) or 1)
    vp = int(parallel_cfg.get("virtual_pipeline_model_parallel_size", 1) or 1)
    cp = int(parallel_cfg.get("context_parallel_size", 1) or 1)
    alignment = parallel_cfg.get("alignment")
    if schedule not in MANUAL_VJP_SCHEDULES:
        raise ValueError(
            f"blocked_1f1b_reason: not a manual-vjp schedule: {schedule!r}"
        )
    if pp <= 1:
        return f"{schedule} requires pipeline_model_parallel_size > 1"
    if vp > 1 and schedule != "1f1b-interleaved":
        return (
            f"the virtual pipeline (virtual_pipeline_model_parallel_size > 1) "
            f"runs under the circular interleaved manual-vjp schedule — set "
            f"pipeline.schedule: 1f1b-interleaved (or auto) — not {schedule}"
        )
    if vp <= 1 and schedule == "1f1b-interleaved":
        return (
            "1f1b-interleaved needs virtual_pipeline_model_parallel_size > 1 "
            "(with vp == 1 there is nothing to interleave; use 1f1b)"
        )
    if cp > 1:
        return (
            f"context parallelism under pp is proven for the autodiff "
            f"wavefront only (blockwise attention vjp inside the manual "
            f"{schedule} tick loop is unvalidated); use schedule: wavefront "
            f"for pp x cp"
        )
    if alignment in ("dpo", "orpo", "kto"):
        return (
            f"preference alignment ({alignment}) pipelines via the "
            f"concatenated-forward wavefront; the manual-vjp schedules "
            f"implement token-level CE only"
        )
    if parallel_cfg.get("lora"):
        return (
            f"LoRA adapters are not wired for the manual-vjp {schedule} head "
            f"(adapter grads on lm_head would be silently dropped)"
        )
    return None


def supports_1f1b(family_refusal: Optional[str], parallel_cfg: dict,
                  schedule: str = "1f1b") -> tuple[bool, str]:
    """Can the manual-vjp ``schedule`` run this model/parallelism combo?
    ``(ok, reason)``; ``reason`` is the first blocking constraint (and what
    ``resolve_schedule`` raises when the config FORCES the schedule).

    ``family_refusal`` is the model's half, asked of its family by the caller
    (``models.family.Family.manual_vjp_refusal``): None where the plain-matmul
    head + token CE the in-loop vocab-sharded head implements is wired, else
    the family's sentence.  ``parallel_cfg`` and ``schedule`` are
    ``blocked_1f1b_reason``'s: the ``distributed_strategy`` sizes plus
    ``alignment`` (None/"sft" or a preference strategy) and ``lora`` (bool).
    """
    blocked = blocked_1f1b_reason(parallel_cfg, schedule) or family_refusal
    if blocked is not None:
        return False, blocked
    return True, f"plain matmul head + token CE ({schedule})"


def resolve_schedule(schedule: str, family_refusal: Optional[str],
                     parallel_cfg: dict) -> str:
    """``pipeline.schedule`` knob -> concrete schedule name.

    ``auto`` picks the memory-bounded manual-vjp family whenever
    ``supports_1f1b`` allows: ``1f1b-interleaved`` when the config carries a
    virtual pipeline (vp > 1 — O(nm*vp) chunk inputs instead of the
    wavefront's ~2x autodiff residuals, and the (pp-1)/(nm*vp) bubble), else
    plain ``1f1b`` (O(pp) in-flight activations).  ``1f1b-zb`` is never
    auto-selected: its deferred-wgrad pass re-linearizes the stage (one
    extra forward per microbatch under remat), a trade the autotune cost
    model prices per plan — force it via the knob or ``tools/plan.py
    --apply`` when the bubble dominates (small nm/pp ratios).  Forcing any
    manual-vjp schedule on an unsupported combo raises with the gate's
    reason instead of failing deep inside shard_map.
    """
    schedule = str(schedule or "auto").lower()
    if schedule not in PIPELINE_SCHEDULES:
        raise ValueError(
            f"pipeline.schedule must be one of {'/'.join(PIPELINE_SCHEDULES)}, "
            f"got {schedule!r}"
        )
    if schedule == "wavefront":
        return "wavefront"
    vp = int(parallel_cfg.get(
        "virtual_pipeline_model_parallel_size", 1) or 1)
    if schedule == "auto":
        preferred = "1f1b-interleaved" if vp > 1 else "1f1b"
        ok, _ = supports_1f1b(family_refusal, parallel_cfg, preferred)
        return preferred if ok else "wavefront"
    ok, reason = supports_1f1b(family_refusal, parallel_cfg, schedule)
    if not ok:
        raise ValueError(
            f"pipeline.schedule: {schedule} is unsupported here: {reason}")
    return schedule


# ---------------------------------------------------------------------------
# Work-compacted schedule tables (schedule as data)
# ---------------------------------------------------------------------------
#
# The manual-vjp executor used to be LOCKSTEP: one scan tick per global tick
# of the classic algebra, every rank executing the full F + head + B (+W)
# body every tick with `jnp.where` masks — a masked tick burned full compute,
# so the priced bubble asymptotics never showed up in wall-clock (the
# documented ~1.25x interleaved-vs-plain gap at pp=2/nm=16/vp=2).  The
# executor below instead iterates over a PRECOMPUTED work table built host
# side per schedule: a static ``[T, pp]`` array of (work_kind, microbatch,
# chunk) entries.  Each scan tick gates its F / head / B / wgrad blocks on
# tick-uniform table flags (``lax.cond`` whose predicate depends only on the
# tick, so every device reaches every collective rendezvous together), which
# compacts a kind's masked ticks out of the executed trip count: a tick no
# rank forwards on costs no forward, a tick no rank backwards on costs no
# backward.
#
# Orderings encoded in the table:
# - plain ``1f1b``: microbatch order; B(m) may share the tick with the head
#   that seeded it (the old dy_next carry cost one tick of latency).
# - ``1f1b-interleaved``: depth-first **m-major pp-group** order (the
#   Megatron interleave): microbatches advance in groups of ``pp`` through
#   all ``vp`` chunks before the next group starts, and the backward walks
#   the same groups with chunks descending.  F and B overlap like plain
#   1F1B instead of serializing chunk-major, and a work item's stage input
#   is consumed O(vp*pp) ticks after its save — the chunk-input store
#   shrinks from O(vp*nm) to a ring bounded by the schedule's true
#   in-flight window (``ring_slot_counts``; priced by
#   ``autotune.cost_model``'s ``pipeline_rings`` term).
# - ``1f1b-zb``: the dgrad tick parks dy and the wgrad for microbatch ``m``
#   runs on EVERY rank at rank 0's dgrad tick (the table's rank-uniform
#   fill) — wgrad ticks are fully dense, the park-ring re-linearization is
#   table data rather than a fixed ``m + 2pp - 1`` slot.
#
# Every ring (stage-input store, forward/backward chunk hand-off, head-dy
# park, zb deferred-dy park) is sized by interval allocation over the
# table's actual write->last-read lifetimes — collision-free by
# construction, asserted at build time.


def _fwd_order(pp: int, nm: int, vp: int) -> list[tuple[int, int]]:
    """Forward work order (chunk, microbatch), shared by every rank."""
    if vp == 1:
        return [(0, m) for m in range(nm)]
    order = []
    for g0 in range(0, nm, pp):
        group = range(g0, min(g0 + pp, nm))
        for c in range(vp):
            order.extend((c, m) for m in group)
    return order


def _bwd_order(pp: int, nm: int, vp: int) -> list[tuple[int, int]]:
    """Backward work order: same pp-groups, chunks descending."""
    if vp == 1:
        return [(0, m) for m in range(nm)]
    order = []
    for g0 in range(0, nm, pp):
        group = range(g0, min(g0 + pp, nm))
        for c in reversed(range(vp)):
            order.extend((c, m) for m in group)
    return order


def _interval_alloc(items: list[tuple[int, int, Any]]
                    ) -> tuple[dict, int]:
    """Greedy register allocation over (write_tick, last_read_tick, key)
    lifetimes -> ({key: slot}, n_slots).

    A slot is reusable only for a write STRICTLY after its previous
    occupant's last read: within one tick the executor's block order does
    run writes before their same-tick reads, but the conservative rule
    keeps every cross-value hazard impossible by construction."""
    out: dict = {}
    busy_until: list[int] = []  # slot -> last read tick of current occupant
    for write, last_read, key in sorted(items, key=lambda it: (it[0], it[1])):
        if last_read < write:
            raise AssertionError(
                f"work table bug: value {key} read at {last_read} before "
                f"its write at {write}")
        for s, until in enumerate(busy_until):
            if until < write:
                out[key] = s
                busy_until[s] = last_read
                break
        else:
            out[key] = len(busy_until)
            busy_until.append(last_read)
    return out, max(1, len(busy_until))


#: per-tick work weights for the table-level bubble accounting: a forward
#: costs ~1 unit, a full-vjp backward ~3 (recompute + dgrad + wgrad), a
#: zb dgrad-only backward ~2, a deferred wgrad ~2 (re-linearize + dW) —
#: the fwd+2xbwd convention split per pullback
_WORK_UNITS = {"f": 1.0, "b_full": 3.0, "b_dgrad": 2.0, "w": 2.0}


@dataclasses.dataclass(frozen=True)
class WorkTable:
    """Host-side compacted schedule for one manual-vjp variant.

    ``rank_cols`` are ``[span, pp]`` arrays (one column per pipe rank, fed
    to the executor pipe-sharded on dim 1); ``glob_cols`` are ``[span]``
    tick-uniform arrays (collective gates and ring bookkeeping — identical
    on every rank by construction, which is what makes the in-scan
    ``lax.cond`` gates rendezvous-safe).  ``ring_sizes`` are the
    interval-allocated slot counts per ring."""

    schedule: str
    pp: int
    nm: int
    vp: int
    span: int
    rank_cols: dict[str, np.ndarray]
    glob_cols: dict[str, np.ndarray]
    ring_sizes: dict[str, int]

    @property
    def lockstep_span(self) -> int:
        """The old one-scan-tick-per-global-tick trip count, for reference."""
        return (2 * self.vp - 1) * self.nm + 2 * self.pp - 1

    def tick_counts(self) -> dict[str, int]:
        g = self.glob_cols
        return {
            "span": self.span,
            "f_ticks": int(g["has_f"].sum()),
            "b_ticks": int(g["has_b"].sum()),
            "w_ticks": int(g["has_w"].sum()),
            "head_ticks": int(g["has_h"].sum()),
            "lockstep_span": self.lockstep_span,
        }

    def bubble_fraction(self) -> float:
        """Predicted idle fraction of the COMPACTED execution: the fraction
        of executed work units that are masked fill/drain slots.  Weighted
        by ``_WORK_UNITS`` — for ``1f1b`` and ``1f1b-interleaved`` the F and
        B windows are equal-length and the weights cancel, reproducing the
        closed-form ``b/(1+b)`` exactly (a tested invariant); for
        ``1f1b-zb`` this is the HONEST SPMD number (the dense wgrad fill
        cannot erase the dgrad chain's fill/drain the way the MPMD ZB-H1
        asymptotic assumes)."""
        wb = _WORK_UNITS["b_dgrad"] if self.schedule == "1f1b-zb" \
            else _WORK_UNITS["b_full"]
        g, r = self.glob_cols, self.rank_cols
        per_tick = (_WORK_UNITS["f"] * g["has_f"]
                    + wb * g["has_b"] + _WORK_UNITS["w"] * g["has_w"])
        executed = self.pp * float(per_tick.sum())
        useful = (_WORK_UNITS["f"] * float(r["f_valid"].sum())
                  + wb * float(r["b_valid"].sum())
                  + _WORK_UNITS["w"] * float(r["w_valid"].sum()))
        return 1.0 - useful / executed if executed > 0 else 0.0


@functools.lru_cache(maxsize=None)
def work_table(schedule: str, pp: int, nm: int, vp: int = 1) -> WorkTable:
    """Build the compacted work table for one manual-vjp schedule.

    Per-rank F/B streams are exact one-tick shifts of rank 0's forward and
    rank ``pp-1``'s backward streams (the ring-hop carries require the
    producing rank's output to be consumed exactly one tick later); the
    variable-latency hand-offs (chunk ring on rank 0, reverse chunk ring on
    rank ``pp-1``, head-dy park, zb deferred-dy park) all ride
    interval-allocated rings, so the streams themselves may compact freely."""
    if schedule not in MANUAL_VJP_SCHEDULES:
        raise ValueError(f"work_table: not a manual-vjp schedule: {schedule!r}")
    if pp <= 1 or nm <= 0:
        raise ValueError(f"work_table needs pp > 1 and nm > 0 (pp={pp}, nm={nm})")
    vp = max(int(vp or 1), 1)
    if (vp > 1) != (schedule == "1f1b-interleaved"):
        raise ValueError(
            f"work_table: schedule {schedule} is inconsistent with vp={vp}")
    zb = schedule == "1f1b-zb"

    # -- rank-0 forward stream (greedy ASAP, one F per tick) ---------------
    t0F: dict[tuple[int, int], int] = {}
    prev = -1
    for c, m in _fwd_order(pp, nm, vp):
        dep = t0F[(c - 1, m)] + pp if c > 0 else 0
        prev = max(prev + 1, dep)
        t0F[(c, m)] = prev
    # head(m) shares the tick of the last rank's final-chunk forward
    tH = {m: t0F[(vp - 1, m)] + pp - 1 for m in range(nm)}

    # -- last-rank backward stream (greedy ASAP, one B per tick) -----------
    tLB: dict[tuple[int, int], int] = {}
    prev = -1
    for c, m in _bwd_order(pp, nm, vp):
        dep = tH[m] if c == vp - 1 else tLB[(c + 1, m)] + pp
        prev = max(prev + 1, dep)
        tLB[(c, m)] = prev
    # zb deferred wgrad: rank-uniform at rank 0's dgrad tick — every rank
    # has parked its dy by then, so wgrad ticks are fully dense (no rank
    # burns a masked wgrad)
    tW = {m: tLB[(0, m)] + pp - 1 for m in range(nm)} if zb else {}

    span = 1 + max(
        max(t for t in t0F.values()) + pp - 1,
        max(t for t in tLB.values()) + pp - 1,
        max(tW.values()) if tW else 0,
    )

    def ri(dtype=np.int32):
        return np.zeros((span, pp), dtype)

    def gi(dtype=np.int32):
        return np.zeros((span,), dtype)

    rank_cols = {
        "f_m": ri(), "f_c": ri(), "f_valid": ri(bool), "f_slot": ri(),
        "b_m": ri(), "b_c": ri(), "b_valid": ri(bool), "b_slot": ri(),
        "w_m": ri(), "w_valid": ri(bool), "w_x_slot": ri(),
        "bdy_slot": ri(), "w_dy_slot": ri(),
    }
    glob_cols = {
        "has_f": gi(bool), "has_b": gi(bool), "has_w": gi(bool),
        "has_h": gi(bool), "h_m": gi(),
        "dyw_slot": gi(), "dyr_slot": gi(),
        "feed_valid": gi(bool), "feed_src": gi(), "feed_slot": gi(),
        "cpark_valid": gi(bool), "cpark_slot": gi(), "cread_slot": gi(),
        "bpark_valid": gi(bool), "bpark_slot": gi(), "bread_slot": gi(),
        "d0_valid": gi(bool), "d0_dst": gi(), "d0_slot": gi(),
    }

    for (c, m), t0 in t0F.items():
        for r in range(pp):
            t = t0 + r
            rank_cols["f_m"][t, r] = m
            rank_cols["f_c"][t, r] = c
            rank_cols["f_valid"][t, r] = True
        if c == 0:
            glob_cols["feed_valid"][t0] = True
            glob_cols["feed_src"][t0] = m % pp
            glob_cols["feed_slot"][t0] = m // pp
    for (c, m), tl in tLB.items():
        for r in range(pp):
            t = tl + (pp - 1 - r)
            rank_cols["b_m"][t, r] = m
            rank_cols["b_c"][t, r] = c
            rank_cols["b_valid"][t, r] = True
        if c == 0:
            t0b = tl + pp - 1  # rank 0's dgrad tick
            glob_cols["d0_valid"][t0b] = True
            glob_cols["d0_dst"][t0b] = m % pp
            glob_cols["d0_slot"][t0b] = m // pp
    for m, t in tH.items():
        glob_cols["has_h"][t] = True
        glob_cols["h_m"][t] = m
    for m, t in tW.items():
        for r in range(pp):
            rank_cols["w_m"][t, r] = m
            rank_cols["w_valid"][t, r] = True
    glob_cols["has_f"] = rank_cols["f_valid"].any(axis=1)
    glob_cols["has_b"] = rank_cols["b_valid"].any(axis=1)
    glob_cols["has_w"] = rank_cols["w_valid"].any(axis=1)

    ring_sizes: dict[str, int] = {}

    # stage-input store: write at the rank's F tick, last read at its B
    # tick (and the rank-uniform wgrad tick under zb)
    n_inflight = 1
    for r in range(pp):
        items = []
        for (c, m), t0 in t0F.items():
            write = t0 + r
            last = tLB[(c, m)] + (pp - 1 - r)
            if zb:
                last = max(last, tW[m])
            items.append((write, last, (c, m)))
        alloc, n = _interval_alloc(items)
        n_inflight = max(n_inflight, n)
        for (c, m), s in alloc.items():
            rank_cols["f_slot"][t0F[(c, m)] + r, r] = s
            rank_cols["b_slot"][tLB[(c, m)] + (pp - 1 - r), r] = s
            if zb:
                rank_cols["w_x_slot"][tW[m], r] = s
    ring_sizes["inflight"] = n_inflight

    # forward chunk hand-off (rank 0): last rank's chunk-c output parks one
    # tick after its F, read by rank 0's F of chunk c+1
    if vp > 1:
        items = [(t0F[(c, m)] + pp, t0F[(c + 1, m)], (c, m))
                 for (c, m) in t0F if c < vp - 1]
        alloc, n = _interval_alloc(items)
        ring_sizes["circ"] = n
        for (c, m), s in alloc.items():
            glob_cols["cpark_valid"][t0F[(c, m)] + pp] = True
            glob_cols["cpark_slot"][t0F[(c, m)] + pp] = s
            glob_cols["cread_slot"][t0F[(c + 1, m)]] = s
        # backward chunk hand-off (rank pp-1): rank 0's chunk-c dgrad parks
        # one tick after its B, read by the last rank's B of chunk c-1
        items = [(tLB[(c, m)] + pp, tLB[(c - 1, m)], (c, m))
                 for (c, m) in tLB if c >= 1]
        alloc, n = _interval_alloc(items)
        ring_sizes["bcirc"] = n
        for (c, m), s in alloc.items():
            glob_cols["bpark_valid"][tLB[(c, m)] + pp] = True
            glob_cols["bpark_slot"][tLB[(c, m)] + pp] = s
            glob_cols["bread_slot"][tLB[(c - 1, m)]] = s
    else:
        ring_sizes["circ"] = ring_sizes["bcirc"] = 0

    # head-dy park: written at the head tick, read by the last rank's
    # final-chunk B (same tick legal: the head block precedes the backward
    # block)
    items = [(tH[m], tLB[(vp - 1, m)], m) for m in range(nm)]
    alloc, n = _interval_alloc(items)
    ring_sizes["dy"] = n
    for m, s in alloc.items():
        glob_cols["dyw_slot"][tH[m]] = s
        glob_cols["dyr_slot"][tLB[(vp - 1, m)]] = s

    # zb deferred-dy park: each rank parks dy at its dgrad tick, reads it
    # at the rank-uniform wgrad tick
    if zb:
        n_wdy = 1
        for r in range(pp):
            items = [(tLB[(0, m)] + (pp - 1 - r), tW[m], m)
                     for m in range(nm)]
            alloc, n = _interval_alloc(items)
            n_wdy = max(n_wdy, n)
            for m, s in alloc.items():
                rank_cols["bdy_slot"][tLB[(0, m)] + (pp - 1 - r), r] = s
                rank_cols["w_dy_slot"][tW[m], r] = s
        ring_sizes["wdy"] = n_wdy
    else:
        ring_sizes["wdy"] = 0

    return WorkTable(schedule=schedule, pp=pp, nm=nm, vp=vp, span=span,
                     rank_cols=rank_cols, glob_cols=glob_cols,
                     ring_sizes=ring_sizes)


def ring_slot_counts(schedule: str, pp: int, nm: int, vp: int = 1
                     ) -> dict[str, int]:
    """Stage-input-sized ring slots the compacted executor allocates for a
    schedule — what ``autotune.cost_model`` prices as ``pipeline_rings``
    (the delta over plain 1f1b, whose buffering the calibrated stage floor
    already absorbs).  Includes a ``total``."""
    sizes = dict(work_table(schedule, pp, nm, vp).ring_sizes)
    sizes["total"] = sum(sizes.values())
    return sizes


def bubble_multiplier(schedule: Optional[str], pp: int, nm: int,
                      vp: int = 1) -> float:
    """Pipeline-bubble work multiplier: fill/drain time as a fraction of the
    schedule's useful in-pipeline work (what ``autotune.cost_model`` charges
    as ``bubble_seconds = multiplier * inner``).

    - ``wavefront`` / ``1f1b``: the classic ``(pp-1)/nm`` — with a virtual
      pipeline the circular interleave cycles microbatches through the ranks
      ``vp`` times, per-rank utilization ``nm*vp/(nm*vp + pp - 1)``
      (``pipeline_loss`` docstring), so the multiplier divides by ``nm*vp``.
    - ``1f1b-interleaved``: same ``(pp-1)/(nm*vp)`` — the interleave is the
      bubble win; the manual vjp changes memory, not fill/drain.
    - ``1f1b-zb``: ``(pp-1)/(3*nm)`` — ZB-H1 asymptotics: with the backward
      split F:dgrad:wgrad ≈ 1:1:1, only the F+dgrad chain needs the
      fill/drain serialization and the deferred wgrad tail fills the
      cooldown, leaving the one-third warmup residual it cannot cover.
    """
    if pp <= 1 or nm <= 0:
        return 0.0
    vp = max(int(vp or 1), 1)
    if schedule == "1f1b-zb":
        return (pp - 1) / (3.0 * nm)
    if schedule == "1f1b":
        return (pp - 1) / float(nm)
    # wavefront + 1f1b-interleaved share the circular-interleave utilization
    return (pp - 1) / float(nm * vp)


def predicted_bubble_fraction(schedule: Optional[str], pp: int, nm: int,
                              vp: int = 1) -> float:
    """Predicted idle fraction of TOTAL pipelined step time — the telemetry
    number (``run_summary.json`` ``bubble_fraction_predicted``);
    0.0 when pp == 1.

    For the manual-vjp schedules this is derived from the COMPACTED work
    table the executor actually runs (``WorkTable.bubble_fraction``): for
    ``1f1b`` and ``1f1b-interleaved`` it equals the closed-form
    ``b / (1 + b)`` exactly (the compacted table realizes the priced
    asymptotics — a tested invariant), while ``1f1b-zb`` reports the honest
    SPMD number (the dense wgrad fill cannot erase the dgrad chain's
    fill/drain the way the MPMD ZB-H1 asymptotic assumes).  The autodiff
    wavefront keeps the closed form."""
    if pp <= 1 or nm <= 0:
        return 0.0
    if schedule in MANUAL_VJP_SCHEDULES:
        # telemetry must not raise on an off-gate combo: normalize vp the
        # way the executor's own dispatch does (interleaved is the only
        # vp>1 schedule; a vp==1 "interleave" degenerates to plain 1f1b)
        vp = max(int(vp or 1), 1) if schedule == "1f1b-interleaved" else 1
        if schedule == "1f1b-interleaved" and vp == 1:
            schedule = "1f1b"
        return work_table(schedule, pp, nm, vp).bubble_fraction()
    b = bubble_multiplier(schedule, pp, nm, vp)
    return b / (1.0 + b)


def _bcast_from(x, is_src):
    """Broadcast the one source rank's ``x`` over the pipe ring: a psum to
    which every other rank contributes zeros.  The sum runs on the bit
    pattern (unsigned, same width), which is exact with a single contributor
    and keeps the wire bytes of ``x.dtype``.  A bf16 psum here does not
    compile on XLA:CPU (jax 0.9.0): inside a partially-manual region JAX puts
    a sharding constraint in the reducer, it becomes a ``copy`` at the
    reducer's root, and the CPU's bf16 all-reduce promotion aborts on it."""
    bits = jnp.dtype(f"uint{8 * jnp.dtype(x.dtype).itemsize}")
    raw = jax.lax.bitcast_convert_type(x, bits)
    out = jax.lax.psum(jnp.where(is_src, raw, jnp.zeros((), bits)), PIPE_AXIS)
    return jax.lax.bitcast_convert_type(out, x.dtype)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree
    )


def ce_denominator(microbatches: dict, *, shift_labels: bool,
                   ignore_index: int = -100) -> jax.Array:
    """Total valid-token count over all microbatches — a function of labels
    only, which is what lets 1F1B seed the CE backward before the forward
    finishes.  Matches the masking in ``ops.cross_entropy``."""
    labels = microbatches["labels"]
    loss_mask = microbatches.get("loss_mask")
    if shift_labels:
        labels = labels[..., 1:]
        loss_mask = None if loss_mask is None else loss_mask[..., 1:]
    valid = (labels != ignore_index).astype(jnp.float32)
    if loss_mask is not None:
        valid = valid * loss_mask.astype(jnp.float32)
    return jnp.sum(valid)


def pipeline_loss_and_grad(
    params: Any,
    layer_params: Any,  # vp==1: [num_layers, ...] dim0 over "pipe";
                        # vp>1: interleaved [vp, pp, Lc, ...] dim1 over "pipe"
    microbatches: dict[str, jax.Array],  # leaves [num_micro, mb, ...]
    *,
    embed_fn: EmbedFn,
    stage_fn: StageFn,
    head_hidden_fn: Callable,  # (head_params, y) -> h   (final norm / identity)
    head_params: Any,          # pytree whose grads flow through head_hidden_fn
    head_weight: jax.Array,    # [V, H] — logits = h @ W.T; pipe-sharded on V
    mesh=None,
    num_microbatches: Optional[int] = None,
    virtual_pipeline_size: int = 1,
    zero_bubble: bool = False,
    stage_aux: bool = False,
    aux_scale: float = 0.0,
    shift_labels: bool = True,
    grad_dtype=jnp.float32,
    ignore_index: int = -100,
    double_buffer: bool = False,
):
    """Manual-vjp pipeline step: returns ``(loss, grads)`` where ``grads``
    has exactly the keys ``{"layers", "params_from_embed", "head_params",
    "head_weight"}`` (a tested invariant — tests/test_pipeline_1f1b.py).

    ``virtual_pipeline_size > 1`` runs the circular interleaved 1F1B
    (``1f1b-interleaved``): layers arrive in the ``to_interleaved``
    ``[vp, pp, Lc, ...]`` layout, microbatches cycle through the ranks
    ``vp`` times in the forward (the wavefront's circular schedule) and the
    backward threads the chunk ring in reverse; like the wavefront it needs
    ``num_microbatches >= pp`` (circular-store write-before-read).
    ``zero_bubble`` runs the ZB-H1-style split (``1f1b-zb``, vp == 1 only):
    the backward tick computes only the activation cotangent (dgrad) so the
    upstream stage unblocks immediately, and the weight-gradient pass for
    microbatch ``m`` is deferred ``rank`` ticks — exactly this rank's
    cooldown-bubble budget — re-linearizing the stage against the saved
    input (the remat trade: one extra stage forward per microbatch).

    - ``layers``: tree shaped/sharded like ``layer_params``;
    - ``params_from_embed``: a PARAMS-shaped tree — the parked cotangent of
      the permuted embed feed has already been pulled through ``jax.vjp`` of
      the embed computation internally, so its ``embed`` entries hold the
      embedding-table grads and every leaf the embed hook does not touch is
      zero.  Add the other grad entries onto it to assemble the full grad
      pytree;
    - ``head_params``: grads of ``head_hidden_fn``'s params (final norm);
    - ``head_weight``: [V, H] grad of the head matmul (transpose into
      ``lm_head.w`` for an untied [H, V] head; add to the embed-table grad
      when tied).

    Loss matches ``pipeline_loss`` (same masking and normalization); the
    caller divides nothing — normalization by the global valid-token count is
    already inside.

    ``double_buffer`` (``distributed_strategy.overlap.pp_double_buffer``)
    moves both stage-hop collective-permutes out of their compute ``cond``s:
    the forward hop issues after the F cond (overlapping the same tick's
    head/backward compute) and the reverse hop defers to the next tick's
    top, ahead of its first read (overlapping that tick's forward compute).
    Gating/data paths are unchanged, so loss and grads are value-identical;
    only the scheduler's freedom changes.
    """
    mesh = mesh or shd.active_mesh()
    pp = int(mesh.shape.get(PIPE_AXIS, 1)) if mesh is not None else 1
    nm = num_microbatches or jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    vp = int(virtual_pipeline_size or 1)
    if pp <= 1:
        raise ValueError("pipeline_loss_and_grad requires pp > 1")
    if zero_bubble and vp > 1:
        raise ValueError(
            "zero_bubble (1f1b-zb) is vp == 1 only; the interleaved chunk "
            "ring has no per-rank cooldown window to defer wgrads into"
        )
    if vp > 1 and nm < pp:
        # chunk c+1 reads the circular store at the tick chunk c's last-rank
        # output is parked only when nm >= pp (same hazard as pipeline_loss)
        raise ValueError(
            f"interleaved pipeline needs num_microbatches >= pp "
            f"(got nm={nm}, pp={pp}, vp={vp})"
        )

    from jax.sharding import PartitionSpec as P

    denom = jnp.maximum(ce_denominator(
        microbatches, shift_labels=shift_labels, ignore_index=ignore_index
    ), 1.0)

    # round-robin embed feed, identical to pipeline_loss: row g = r*slots + l
    # <-> microbatch m = l*pp + r, dim 0 sharded over pipe
    slots = -(-nm // pp)
    g = np.arange(pp * slots)
    m_of_g = (g % slots) * pp + g // slots
    m_idx = np.where(m_of_g < nm, m_of_g, 0)
    mb_perm = jax.tree_util.tree_map(lambda x: x[m_idx], microbatches)

    def emb_of(p):
        e = jax.vmap(lambda m: embed_fn(p, m))(mb_perm)
        unc = P.UNCONSTRAINED
        return shd.constrain(e, P(PIPE_AXIS, *([unc] * (e.ndim - 1))))

    emb, emb_vjp = jax.vjp(emb_of, params)

    # the compacted schedule as data: per-rank work entries ride into the
    # manual region pipe-sharded on their rank dim, tick-uniform gate/ring
    # columns replicated (see work_table)
    schedule_name = ("1f1b-zb" if zero_bubble
                     else ("1f1b-interleaved" if vp > 1 else "1f1b"))
    table = work_table(schedule_name, pp, nm, vp)
    wt_rank = {k: jnp.asarray(v) for k, v in table.rank_cols.items()}
    wt_glob = {k: jnp.asarray(v) for k, v in table.glob_cols.items()}

    body = functools.partial(
        _onef1b_body,
        stage_fn=stage_fn, head_hidden_fn=head_hidden_fn, pp=pp, nm=nm,
        vp=vp, zero_bubble=zero_bubble, rings=table.ring_sizes,
        slots=slots, stage_aux=stage_aux, aux_scale=float(aux_scale),
        shift_labels=shift_labels, grad_dtype=grad_dtype,
        ignore_index=ignore_index, double_buffer=bool(double_buffer),
    )
    layer_spec = P(None, PIPE_AXIS) if vp > 1 else P(PIPE_AXIS)
    vocab_spec = P(PIPE_AXIS, *([None] * (head_weight.ndim - 1)))
    fn = shd.shard_map(
        body,
        mesh=mesh,
        in_specs=(layer_spec, P(), P(), vocab_spec, P(PIPE_AXIS), P(),
                  P(None, PIPE_AXIS), P()),
        out_specs=(P(), layer_spec, P(PIPE_AXIS), vocab_spec, P(), P()),
        axis_names={PIPE_AXIS},
        check_vma=False,
    )
    loss_sum, d_layers, d_emb, d_w, d_head_params, aux_total = fn(
        layer_params, head_params, microbatches, head_weight, emb, denom,
        wt_rank, wt_glob,
    )
    loss = loss_sum / denom + aux_scale * aux_total
    (d_params_embed,) = emb_vjp(d_emb.astype(emb.dtype))
    grads = {
        "layers": d_layers,
        "params_from_embed": d_params_embed,
        "head_params": d_head_params,
        "head_weight": d_w,
    }
    return loss, grads


def _onef1b_body(local_layers, head_params, microbatches, w_r, emb, denom,
                 wt_rank, wt_glob, *,
                 stage_fn, head_hidden_fn, pp, nm, vp, zero_bubble, rings,
                 slots, stage_aux, aux_scale, shift_labels, grad_dtype,
                 ignore_index, double_buffer=False):
    """Per-pipe-rank WORK-COMPACTED manual-vjp tick loop (inside shard_map,
    manual "pipe").

    The schedule is DATA, not control flow: one ``lax.scan`` over the
    compacted work table (``work_table`` — ``wt_rank`` carries this rank's
    per-tick (kind, microbatch, chunk, ring-slot) entries pipe-sharded on
    their rank dim, ``wt_glob`` the tick-uniform gates and ring
    bookkeeping).  Each tick gates its forward / head / backward / wgrad
    blocks on the table's ``has_*`` flags with ``lax.cond``: the predicates
    are tick-only (identical on every device), so every collective inside a
    taken branch — ring hops, head psums, embed feed and embed-cotangent
    routing switches — still reaches its rendezvous on every device, while
    a tick no rank forwards (backwards) on executes no stage compute at
    all.  That is what cashes the priced bubble in wall-clock: the old
    lockstep loop burned the full body on all
    ``(2*vp - 1)*nm + 2*pp - 1`` ticks, the compacted loop runs F on
    ``nm*vp + pp - 1`` ticks and B on ``nm*vp + pp - 1`` ticks (dense for
    ``nm % pp == 0`` — the m-major pp-group interleave order overlaps the
    F/B windows like plain 1F1B instead of serializing chunk-major).

    Stream alignment: rank ``r``'s F(c, m) runs exactly one tick after rank
    ``r-1``'s (the forward ring-hop carry), rank ``r``'s B(c, m) exactly
    one tick after rank ``r+1``'s (the reverse hop) — per-rank streams are
    shifts of the table's rank-0 forward / last-rank backward streams.
    Variable-latency hand-offs ride interval-allocated rings instead of
    carry slots: the stage-input store (``inflight``), the forward chunk
    ring on rank 0 (``circ``), the backward chunk ring on rank ``pp-1``
    (``bcirc``), the head-dy park (``dy_ring`` — the head may seed its B
    the SAME tick now), and zb's deferred-dy park (``wdy_ring``).  Under
    ``zero_bubble`` the B tick computes dgrad only and the wgrad for
    microbatch ``m`` runs at the table's rank-uniform fill tick — same dy,
    same saved input, grads bitwise the plain-1F1B split into two
    pullbacks."""
    rank = jax.lax.axis_index(PIPE_AXIS)
    is_first = rank == 0
    is_last = rank == pp - 1
    vr = w_r.shape[0]  # local vocab slice size

    x0 = emb[0]
    cyclic = [(i, (i + 1) % pp) for i in range(pp)]
    reverse = [((i + 1) % pp, i) for i in range(pp)]

    # normalize local layer layout: vp>1 arrives [vp, 1, Lc, ...] (dim1 is
    # the pipe shard) -> [vp, Lc, ...]; vp==1 stays flat [Lc, ...]
    if vp > 1:
        local_layers = jax.tree_util.tree_map(
            lambda x: jnp.squeeze(x, axis=1), local_layers
        )

    def chunk_layers(c):
        if vp == 1:
            return local_layers
        return jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            local_layers,
        )

    def stage_flat(lp, x, mb, c):
        out = stage_fn(lp, x, {**mb, "_chunk": jnp.asarray(c, jnp.int32)})
        if stage_aux:
            return out
        return out, jnp.zeros((), jnp.float32)

    def acc_layers(dl, d_lp, c, bv):
        """Accumulate a chunk's weight grads (into chunk row c when vp>1)."""
        if vp == 1:
            return jax.tree_util.tree_map(
                lambda a, gkk: a + bv * gkk.astype(grad_dtype), dl, d_lp
            )

        def one(a, gkk):
            cur = jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                a, cur + bv * gkk.astype(grad_dtype), c, 0
            )

        return jax.tree_util.tree_map(one, dl, d_lp)

    def ring_at(ring, slot):
        return jax.lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)

    def ring_put(ring, slot, value, valid):
        cur = ring_at(ring, slot)
        return jax.lax.dynamic_update_index_in_dim(
            ring, jnp.where(valid, value, cur), slot, 0
        )

    def tick(carry, xt):
        (recv, cot_recv, inflight, circ, bcirc, dy_ring, wdy_ring,
         d_layers, d_emb, d_w, d_hp_acc, loss_acc, aux_acc) = carry

        if double_buffer:
            # double-buffered reverse hop: ``cot_recv`` carries the UNHOPPED
            # dgrad parked by the previous tick's b_block; it hops here at
            # the tick top — gated on the table's shifted has_b column, the
            # write->first-read interval the compacted schedule guarantees —
            # so the collective-permute overlaps this tick's forward compute
            # instead of serializing inside last tick's backward cond.  Its
            # consumer (this tick's b_block / bcirc park) reads the hopped
            # value exactly as the in-cond form did: value-identical.
            cot_recv = jax.lax.cond(
                xt["hop_b"],
                lambda: jax.lax.ppermute(cot_recv, PIPE_AXIS, reverse),
                lambda: cot_recv,
            )

        # ---- chunk hand-off parks (values hopped at the previous tick) -
        # recv holds the predecessor's y from tick t-1: on rank 0 that is
        # the last rank's output, parked for its next chunk; cot_recv holds
        # the successor's dgrad: on rank pp-1 that is rank 0's, parked for
        # the previous chunk's B tick.  The parked value is only meaningful
        # on the owning rank (other ranks park garbage in their local ring,
        # never read — the same SPMD trade the wavefront makes).
        if vp > 1:
            circ = ring_put(circ, xt["cpark_slot"], recv, xt["cpark_valid"])
            bcirc = ring_put(bcirc, xt["bpark_slot"], cot_recv,
                             xt["bpark_valid"])

        # ---- forward work ----------------------------------------------
        m_F, c_F, f_valid = xt["f_m"], xt["f_c"], xt["f_valid"]

        def f_block(inflight):
            mbF = _tree_index(microbatches, m_F)
            # rank 0 consumes microbatch m_F's embedding at its chunk-0 F
            # tick: fetch it from its round-robin owner.  Branch index and
            # gate are table columns — tick-uniform on every device.
            e_t = jax.lax.dynamic_index_in_dim(
                emb, xt["feed_slot"], 0, keepdims=False
            )
            fresh = jax.lax.cond(
                xt["feed_valid"],
                lambda: jax.lax.switch(
                    xt["feed_src"],
                    [functools.partial(
                        jax.lax.ppermute, e_t, PIPE_AXIS, [(o, 0)]
                    ) for o in range(pp)],
                ),
                lambda: jnp.zeros(x0.shape, x0.dtype),
            )
            if vp > 1:
                parked_in = ring_at(circ, xt["cread_slot"])
                first_in = jnp.where(c_F == 0, fresh, parked_in)
            else:
                first_in = fresh
            x_in = jnp.where(is_first, first_in, recv)
            y, s_aux = stage_flat(chunk_layers(c_F), x_in, mbF, c_F)
            # save the stage input for this rank's B (and zb wgrad) tick
            inflight = ring_put(inflight, xt["f_slot"], x_in, f_valid)
            if double_buffer:
                # hop hoisted out of this cond (issued below, after the
                # cond) so it can overlap the head/backward compute
                return y, s_aux, inflight, recv
            # forward ring hop: consumed by the successor's F next tick
            hop = jax.lax.ppermute(y, PIPE_AXIS, cyclic)
            return y, s_aux, inflight, hop

        y, s_aux, inflight, recv = jax.lax.cond(
            xt["has_f"], f_block,
            lambda inflight: (jnp.zeros(x0.shape, x0.dtype),
                              jnp.zeros((), jnp.float32), inflight, recv),
            inflight,
        )
        if double_buffer:
            # hoisted forward hop: a cond branch is an atomic unit to XLA,
            # so the in-cond permute serialized between this tick's stage
            # compute and its head/backward blocks; standing alone it only
            # depends on ``y`` and overlaps both
            recv = jax.lax.cond(
                xt["has_f"],
                lambda: jax.lax.ppermute(y, PIPE_AXIS, cyclic),
                lambda: recv,
            )
        aux_acc = aux_acc + jnp.where(f_valid, s_aux, 0.0)

        # ---- head + CE (vocab sliced over pipe) ------------------------
        def h_block(dy_ring, d_w, d_hp_acc, loss_acc):
            # the head tick IS the last rank's final-chunk F tick: broadcast
            # its fresh output over the pipe ring, then every rank computes
            # logits for its V/pp vocab slice
            m_H = xt["h_m"]
            y_bcast = _bcast_from(
                y,
                jnp.logical_and(is_last,
                                jnp.logical_and(f_valid, c_F == vp - 1)),
            )
            mbH = _tree_index(microbatches, m_H)
            # hidden fn under vjp over BOTH (hp, y) so the norm-weight grad
            # and dy fall out of one pass; the CE backward is closed-form
            (h_out, head_vjp) = jax.vjp(head_hidden_fn, head_params, y_bcast)
            if shift_labels:
                h2 = h_out[:, :-1]
                labels2 = mbH["labels"][:, 1:]
                lmH = mbH.get("loss_mask")
                lm2 = None if lmH is None else lmH[:, 1:]
            else:
                h2 = h_out
                labels2 = mbH["labels"]
                lmH = mbH.get("loss_mask")
                lm2 = lmH
            valid = labels2 != ignore_index
            safe = jnp.where(valid, labels2, 0)
            mask = valid.astype(jnp.float32)
            if lm2 is not None:
                mask = mask * lm2.astype(jnp.float32)
            logits = jnp.einsum(
                "bsh,vh->bsv", h2, w_r, preferred_element_type=jnp.float32
            )
            gmax = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), PIPE_AXIS
            )
            shifted = logits - gmax[..., None]
            sumexp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1),
                                  PIPE_AXIS)
            lse = jnp.log(sumexp) + gmax
            off = rank * vr
            onehot = (
                jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                         logits.ndim - 1)
                + off == safe[..., None]
            )
            ll = jax.lax.psum(
                jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1), PIPE_AXIS
            )
            loss_m = jnp.sum((lse - ll) * mask)
            p_r = jnp.exp(shifted) / sumexp[..., None]
            dlogits = (p_r - onehot.astype(jnp.float32)) \
                * (mask / denom)[..., None]
            dlogits = dlogits.astype(h2.dtype)
            d_wr_t = jnp.einsum(
                "bsv,bsh->vh", dlogits, h2, preferred_element_type=jnp.float32
            )
            dh2 = jax.lax.psum(
                jnp.einsum("bsv,vh->bsh", dlogits, w_r,
                           preferred_element_type=jnp.float32),
                PIPE_AXIS,
            ).astype(h_out.dtype)
            if shift_labels:
                dh = jnp.pad(
                    dh2, ((0, 0), (0, 1)) + ((0, 0),) * (dh2.ndim - 2)
                )
            else:
                dh = dh2
            d_hp_t, dy_t = head_vjp(dh)
            loss_acc = loss_acc + loss_m
            d_w = d_w + d_wr_t.astype(grad_dtype)
            d_hp_acc = jax.tree_util.tree_map(
                lambda a, gkk: a + gkk.astype(grad_dtype), d_hp_acc, d_hp_t
            )
            # park dy for the last rank's final-chunk B (same tick legal:
            # this block precedes the backward block)
            dy_ring = ring_put(dy_ring, xt["dyw_slot"],
                               dy_t.astype(x0.dtype), True)
            return dy_ring, d_w, d_hp_acc, loss_acc

        dy_ring, d_w, d_hp_acc, loss_acc = jax.lax.cond(
            xt["has_h"], h_block, lambda *a: a,
            dy_ring, d_w, d_hp_acc, loss_acc,
        )

        # ---- backward (full vjp, or dgrad-only under zero_bubble) ------
        m_B, c_B, b_valid = xt["b_m"], xt["b_c"], xt["b_valid"]

        def b_block(wdy_ring, d_layers, d_emb):
            mbB = _tree_index(microbatches, m_B)
            x_saved = ring_at(inflight, xt["b_slot"])
            dy_parked = ring_at(dy_ring, xt["dyr_slot"])
            if vp > 1:
                last_dy = jnp.where(
                    c_B == vp - 1, dy_parked,
                    ring_at(bcirc, xt["bread_slot"]),
                )
            else:
                last_dy = dy_parked
            dy_in = jnp.where(is_last, last_dy, cot_recv)
            seed = (dy_in.astype(x0.dtype),
                    jnp.asarray(aux_scale, jnp.float32))
            bv = b_valid.astype(jnp.float32)
            lp_B = chunk_layers(c_B)

            if zero_bubble:
                # dgrad only: the activation cotangent unblocks the
                # upstream stage this tick; dy parks for the table's
                # deferred wgrad fill tick
                _, x_vjp = jax.vjp(lambda x: stage_flat(lp_B, x, mbB, c_B),
                                   x_saved)
                (d_x_t,) = x_vjp(seed)
                wdy_ring = ring_put(wdy_ring, xt["bdy_slot"], dy_in, b_valid)
            else:
                def stage_for_vjp(lp, x):
                    return stage_flat(lp, x, mbB, c_B)

                _, stage_vjp = jax.vjp(stage_for_vjp, lp_B, x_saved)
                d_lp_t, d_x_t = stage_vjp(seed)
                d_layers = acc_layers(d_layers, d_lp_t, c_B, bv)
            d_x_masked = jnp.where(b_valid, d_x_t, jnp.zeros_like(d_x_t))

            # embed cotangent: rank 0's chunk-0 d_x routes back to its
            # round-robin owner (the reverse of the embed feed) — gate and
            # destination are table columns, tick-uniform
            d_x0 = jnp.where(is_first, d_x_masked, jnp.zeros_like(d_x_masked))
            routed = jax.lax.cond(
                xt["d0_valid"],
                lambda: jax.lax.switch(
                    xt["d0_dst"],
                    [functools.partial(
                        jax.lax.ppermute, d_x0, PIPE_AXIS, [(0, o)]
                    ) for o in range(pp)],
                ),
                lambda: jnp.zeros_like(d_x0),
            )
            mine = jnp.logical_and(xt["d0_valid"], xt["d0_dst"] == rank)
            d_emb = ring_put(d_emb, xt["d0_slot"],
                             routed.astype(grad_dtype), mine)
            if double_buffer:
                # park the dgrad unhopped; the deferred hop at the NEXT
                # tick's top delivers it before its first read (the final
                # tick's pending value has no consumer — the table would
                # otherwise have scheduled another B — so never hopping it
                # is safe)
                return wdy_ring, d_layers, d_emb, d_x_masked
            # reverse ring hop: consumed by the predecessor's B next tick
            cot_hop = jax.lax.ppermute(d_x_masked, PIPE_AXIS, reverse)
            return wdy_ring, d_layers, d_emb, cot_hop

        wdy_ring, d_layers, d_emb, cot_recv = jax.lax.cond(
            xt["has_b"], b_block,
            lambda wdy_ring, d_layers, d_emb: (wdy_ring, d_layers, d_emb,
                                               cot_recv),
            wdy_ring, d_layers, d_emb,
        )

        # ---- deferred wgrad (zb fill ticks — rank-uniform, fully dense) -
        if zero_bubble:
            def w_block(d_layers):
                m_W = xt["w_m"]
                mbW = _tree_index(microbatches, m_W)
                x_w = ring_at(inflight, xt["w_x_slot"])
                dy_w = ring_at(wdy_ring, xt["w_dy_slot"])
                _, lp_vjp = jax.vjp(
                    lambda lp: stage_flat(lp, x_w, mbW,
                                          jnp.zeros((), jnp.int32)),
                    local_layers,
                )
                (d_lp_w,) = lp_vjp(
                    (dy_w.astype(x0.dtype),
                     jnp.asarray(aux_scale, jnp.float32))
                )
                return acc_layers(d_layers, d_lp_w, 0,
                                  xt["w_valid"].astype(jnp.float32))

            d_layers = jax.lax.cond(
                xt["has_w"], w_block, lambda d_layers: d_layers, d_layers
            )

        return (recv, cot_recv, inflight, circ, bcirc, dy_ring, wdy_ring,
                d_layers, d_emb, d_w, d_hp_acc, loss_acc, aux_acc), None

    zeros = jnp.zeros_like(x0)
    inflight0 = jnp.zeros((rings["inflight"],) + x0.shape, x0.dtype)
    circ0 = (jnp.zeros((rings["circ"],) + x0.shape, x0.dtype) if vp > 1
             else jnp.zeros((1, 1), x0.dtype))
    bcirc0 = (jnp.zeros((rings["bcirc"],) + x0.shape, x0.dtype) if vp > 1
              else jnp.zeros((1, 1), x0.dtype))
    dy_ring0 = jnp.zeros((rings["dy"],) + x0.shape, x0.dtype)
    wdy_ring0 = (jnp.zeros((rings["wdy"],) + x0.shape, x0.dtype)
                 if zero_bubble else jnp.zeros((1, 1), x0.dtype))
    d_layers0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, grad_dtype), local_layers
    )
    d_emb0 = jnp.zeros((slots,) + x0.shape, grad_dtype)
    d_w0 = jnp.zeros(w_r.shape, grad_dtype)
    d_hp0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, grad_dtype), head_params
    )
    carry0 = (zeros, jnp.zeros_like(x0), inflight0,
              circ0, bcirc0, dy_ring0, wdy_ring0, d_layers0, d_emb0, d_w0,
              d_hp0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    # per-rank columns arrive [T, 1] (pipe-sharded on dim 1) -> [T]; the
    # scan consumes one row of the table per compacted tick
    xs = {**{k: v[:, 0] for k, v in wt_rank.items()}, **wt_glob}
    if double_buffer:
        # tick-uniform gate for the deferred reverse hop: "did the PREVIOUS
        # tick run a backward" — has_b shifted one tick right (the pending
        # dgrad parked at t-1 hops at the top of t)
        hb = xs["has_b"]
        xs["hop_b"] = jnp.concatenate([jnp.zeros((1,), hb.dtype), hb[:-1]])
    carry, _ = jax.lax.scan(tick, carry0, xs)
    (_, _, _, _, _, _, _, d_layers, d_emb, d_w, d_hp_acc, loss_acc,
     aux_acc) = carry
    if vp > 1:
        # restore the interleaved [vp, 1, Lc, ...] local layout (dim1 is
        # this rank's pipe shard) so the out spec reassembles [vp, pp, Lc]
        d_layers = jax.tree_util.tree_map(lambda x: x[:, None], d_layers)
    aux_total = jax.lax.psum(aux_acc, PIPE_AXIS)
    # loss and head grads are computed identically on every rank (the CE is
    # psum-closed over pipe); d_w is this rank's vocab slice
    return loss_acc, d_layers, d_emb, d_w, d_hp_acc, aux_total
