"""Sharding rules and constraint helpers.

Where the reference wires explicit NxD parallel layers and hand-written
scatter/gather calls (``ColumnParallelLinear``/``RowParallelLinear``/
``scatter_to_sequence_parallel_region`` — reference ``modeling_llama.py:74-78``,
``modeling_mixtral.py:677-679``), the TPU-native design expresses *all* of
TP/SP/CP/DP as PartitionSpecs:

- tensor parallelism   = weight specs over the ``model`` axis
- sequence parallelism = activation seq-dim constrained to ``model`` between blocks
- context parallelism  = activation seq-dim constrained to ``context``
- data parallelism     = batch dim over the compound ``(data, expert)`` axis

XLA/GSPMD then inserts exactly the all-gathers/reduce-scatters the reference's
layers perform by hand.  ``constrain`` is a mesh-aware
``with_sharding_constraint`` that no-ops when no mesh is active, so every model
function also runs unsharded (unit tests, single host).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.parallel.mesh import DATA_AXES

_STATE = threading.local()


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=frozenset(),
              check_vma=True):
    """``jax.shard_map``.  ``axis_names`` empty means every mesh axis (fully
    manual); a set gives partial manualness (the pipeline body is Manual over
    ``pipe`` only, and GSPMD keeps sharding data/model inside)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=axis_names, check_vma=check_vma)


def active_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


def manual_axes() -> frozenset:
    """Mesh axes that are Manual where this is traced: empty outside any
    ``shard_map``, ``{"pipe"}`` inside the pipeline body."""
    cur = jax.sharding.get_abstract_mesh()
    return frozenset(
        n for n, t in zip(cur.axis_names, cur.axis_types)
        if t == jax.sharding.AxisType.Manual
    )


def region_mesh():
    """``(mesh, outer_manual)`` for a ``shard_map`` opened where this is
    traced: the active mesh and no manual axes, or, inside a manual region
    (the pipeline body, manual over ``pipe``), the context's abstract mesh and
    the axes already manual there, which the new region must leave out of its
    ``axis_names``.  ``(None, frozenset())`` with no mesh active."""
    mesh = active_mesh()
    if mesh is None:
        return None, frozenset()
    manual = manual_axes()
    if manual:
        mesh = jax.sharding.get_abstract_mesh()
    return mesh, manual


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for ``constrain``/``named_sharding`` inside the block."""
    prev = active_mesh()
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def trace_facts() -> Optional[dict]:
    """The dict ``collect_trace_facts`` is filling on this thread, else None."""
    return getattr(_STATE, "facts", None)


@contextlib.contextmanager
def collect_trace_facts():
    """Collect what code traced inside the block records about how it was
    partitioned (``ops.moe``: ``moe_token_shards``) or how its kernels walk
    (``ops.flash_attention``: ``flash_band``).  The facts are of the trace: a
    function whose jaxpr is already cached records nothing."""
    prev = trace_facts()
    _STATE.facts = facts = {}
    try:
        yield facts
    finally:
        _STATE.facts = prev


def named_sharding(spec: P, mesh: Optional[Mesh] = None) -> NamedSharding:
    m = mesh or active_mesh()
    if m is None:
        raise RuntimeError("no active mesh; wrap in parallel.sharding.use_mesh(mesh)")
    return NamedSharding(m, spec)


def constrain(x, spec: Optional[P], mesh: Optional[Mesh] = None):
    """``with_sharding_constraint`` if a mesh is active, else identity.

    Prefers the bare-PartitionSpec form, which resolves against the *context*
    mesh — required inside ``shard_map`` regions (e.g. the pipeline body, which
    is Manual over ``pipe``), where a NamedSharding built from the outer
    all-Auto mesh would conflict.  Falls back to an explicit NamedSharding when
    no context mesh is set.
    """
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except RuntimeError as e:
        # ONLY the no-context-mesh case falls through (plain jit under the
        # legacy `with mesh:` manager); a genuine spec error (bad axis, rank
        # mismatch — ValueError) must propagate, not silently return
        # unconstrained activations.
        if "non-empty mesh" not in str(e):
            raise
        m = mesh or active_mesh()
        if m is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))


def spec_errors(specs, mesh: Mesh) -> list[str]:
    """Static PartitionSpec lint over a spec pytree: every named axis must
    exist in ``mesh`` and no axis may be used twice within one spec (XLA
    rejects the latter late, with a partitioner error that names neither the
    leaf nor the axis).  Returns curated ``path: problem`` strings; empty
    means clean.  The pre-flight graph auditor runs this before lowering so
    a bad spec dies with a leaf path instead of a GSPMD traceback."""
    known = set(mesh.axis_names)
    errors: list[str] = []

    def visit(path, spec):
        if spec is None or not isinstance(spec, P):
            return spec
        where = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                         for p in path) or "<root>"
        seen: set[str] = set()
        for dim in spec:
            for ax in (dim if isinstance(dim, tuple) else (dim,)):
                if ax is None:
                    continue
                if ax not in known:
                    errors.append(
                        f"{where}: spec {spec} names axis {ax!r} absent from "
                        f"mesh axes {sorted(known)}"
                    )
                elif ax in seen:
                    errors.append(
                        f"{where}: spec {spec} uses axis {ax!r} twice — one "
                        f"mesh axis cannot shard two tensor dims"
                    )
                seen.add(ax)
        return spec

    jax.tree_util.tree_map_with_path(
        visit, specs, is_leaf=lambda x: isinstance(x, P) or x is None,
    )
    return errors


def validate_specs(specs, mesh: Mesh) -> None:
    """Raise ``ValueError`` listing every defect ``spec_errors`` finds."""
    errors = spec_errors(specs, mesh)
    if errors:
        raise ValueError(
            "invalid PartitionSpecs:\n  " + "\n  ".join(errors[:20])
            + (f"\n  ... and {len(errors) - 20} more" if len(errors) > 20
               else "")
        )


def seq_axes(sequence_parallel: bool, context_parallel: bool):
    """Mesh axes the activation sequence dim is sharded over between blocks.

    CP splits the sequence first (outer), Megatron-SP shards the remainder over
    the TP group (reference composes them the same way: CP batch-level split at
    ``base.py:199``, then per-layer SP inside NxD layers)."""
    axes = []
    if context_parallel:
        axes.append("context")
    if sequence_parallel:
        axes.append("model")
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def act_spec(sequence_parallel: bool = False, context_parallel: bool = False) -> P:
    """Spec for block-boundary activations ``[batch, seq, hidden]``."""
    return P(DATA_AXES, seq_axes(sequence_parallel, context_parallel), None)


def heads_spec(context_parallel: bool = False) -> P:
    """Spec for attention-internal activations ``[batch, seq, heads, head_dim]``:
    heads over ``model`` (TP), seq over ``context`` only (attention needs the
    full TP-group sequence — the all-gather GSPMD inserts here is the reference's
    pre-QKV all-gather under SP)."""
    return P(DATA_AXES, "context" if context_parallel else None, "model", None)


def logits_spec(context_parallel: bool = False) -> P:
    """Spec for lm-head logits ``[batch, seq, vocab]``: vocab over ``model``
    (the reference's no-gather ColumnParallel lm_head + parallel_cross_entropy,
    ``modeling_llama.py:808-833``)."""
    return P(DATA_AXES, "context" if context_parallel else None, "model")
