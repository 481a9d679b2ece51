"""What ``correct`` compares, and its limits.

Program side: per-leaf norms read from the live trainer during set-up.
Reference side: the configuration's reference module (``cell.reference``,
``harness/cell.py``).  Limits: ``benchmark/limits/<configuration>.json``, else
the configuration's entry in ``benchmark/limits.json``; each set from chip
readings that PERF.md lists.
"""

from __future__ import annotations

import json
import re
import statistics
from pathlib import Path
from typing import Any, Mapping, Optional

from benchmark.harness import say
from benchmark.harness.cell import ROOT


def _leaf_norms(reference, tree) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    return dict(zip(reference.leaf_names(tree),
                    (float(x) for x in jax.tree_util.tree_leaves(norms))))


def first_gradient_norms(reference, opt_state: Mapping[str, Any],
                         beta1: float) -> dict:
    """Per-leaf norm of the first gradient as the optimizer got it (after
    clipping), worked out from its state after ONE update: the first moment
    is then ``(1 - beta1) g``."""
    return {k: v / (1.0 - beta1)
            for k, v in _leaf_norms(reference, opt_state["mu"]).items()}


def parameter_change_norms(reference, params, model: Mapping[str, Any],
                           seed: int) -> dict:
    """Per-leaf norm of ``params - weights(seed)``, the seeded weights made
    again by the reference's recipe, leaf by leaf where the trainer's lie."""
    import jax
    import jax.numpy as jnp

    shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)

    @jax.jit
    def change(p, key):
        init = jax.lax.with_sharding_constraint(
            reference.init_params(model, key), shardings)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b))),
            p, init)

    return dict(zip(reference.leaf_names(params),
                    (float(x) for x in jax.tree_util.tree_leaves(
                        change(params, jax.random.PRNGKey(int(seed)))))))


def sharder(devices):
    """How the reference lays a tree over several chips: every leaf split on
    its last dimension that divides evenly (vectors stay whole).  One chip:
    nothing to do."""
    n = len(devices)
    if n == 1:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("x",))

    def spec(a):
        dims = [None] * a.ndim
        for i in reversed(range(a.ndim)):
            if a.ndim >= 2 and a.shape[i] % n == 0 and a.shape[i] >= 128 * n:
                dims[i] = "x"
                break
        return NamedSharding(mesh, P(*dims))

    return lambda tree: jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, spec(a)), tree)


def limits_for(config_name: str, root: Path = ROOT) -> dict:
    """The configuration's limits: its own file where it has one, else its
    entry in the table.  One place only: a name in both is an error."""
    own = root / "benchmark" / "limits" / f"{config_name}.json"
    with open(root / "benchmark" / "limits.json") as f:
        table = json.load(f)
    if own.exists():
        if config_name in table:
            raise ValueError(f"{config_name!r} has limits in benchmark/limits.json "
                             f"and in benchmark/limits/{own.name}")
        with open(own) as f:
            return json.load(f)
    if config_name not in table:
        raise KeyError(f"neither benchmark/limits/{own.name} nor "
                       f"benchmark/limits.json has limits for {config_name!r}")
    return table[config_name]


def leaf_gaps(program: Mapping[str, float], ref: Mapping[str, float]) -> dict:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' gradients are all but zero)."""
    if sorted(program) != sorted(ref):
        raise ValueError(
            f"leaves differ: program {sorted(program)} vs reference {sorted(ref)}")
    median = statistics.median(ref.values())
    return {k: abs(program[k] - ref[k]) / max(ref[k], median, 1e-30) for k in ref}


def numbers(program: Mapping[str, Any], ref: Mapping[str, Any],
            routed: Optional[str] = None) -> dict:
    """Every number compared: name -> (value, detail).  ``routed`` is a
    pattern of leaf paths (router and experts) whose gradients hang on
    discrete routing decisions: a token whose second and third experts are
    all but tied changes sides on the last bit, so those leaves get numbers,
    and limits, of their own (PERF.md)."""
    out = {}
    for k, (p, r) in enumerate(zip(program["loss"], ref["loss"]), start=1):
        out[f"loss_gap_step{k}"] = (abs(p - r), f"program {p:.6f} reference {r:.6f}")
    rx = re.compile(routed) if routed else None
    for what in ("grad1", "dparam"):
        gaps = leaf_gaps(program[what], ref[what])
        groups = {f"{what}_worst_leaf": {
            k: v for k, v in gaps.items() if not (rx and rx.search(k))}}
        if rx:
            groups[f"{what}_routed_worst_leaf"] = {
                k: v for k, v in gaps.items() if rx.search(k)}
        for name, group in groups.items():
            leaf = max(group, key=group.get)
            out[name] = (group[leaf], f"{leaf}: program {program[what][leaf]:.6g} "
                                      f"reference {ref[what][leaf]:.6g}")
    return out


def beside_limits(compared: Mapping[str, Any]) -> dict:
    """Each number compared beside its limit, as a line of text by its name."""
    return {name: f"check: {name} {compared[name]:.4e} limit {limit:.4e} "
                  f"{'ok' if compared[name] <= limit else 'FAILED'}"
            for name, limit in compared["limits"].items()}


def compare(program: Mapping[str, Any], ref: Mapping[str, Any],
            limits: Mapping[str, Any]) -> tuple:
    """Print each number beside its limit.  Returns (all inside, the numbers,
    with their limits under ``limits``)."""
    found = numbers(program, ref, limits.get("routed_leaves"))
    compared = {k: v for k, (v, _) in found.items()}
    compared["limits"] = {k: float(limits[k.split("_step")[0]]) for k in found}
    for name, line in beside_limits(compared).items():
        say(f"{line} ({found[name][1]})")
    ok = all(compared[k] <= limit for k, limit in compared["limits"].items())
    compared["leaves"] = {what: leaf_gaps(program[what], ref[what])
                          for what in ("grad1", "dparam")}
    return ok, compared
