"""Device time by the program's own layer names.  Every ``XLA Ops`` event's
metadata carries ``tf_op``, JAX's name stack for the operation
(``benchmark/xplane_meta.py``); the program's ``jax.named_scope`` names are
components of it, bare inside a scanned layer (``.../closed_call/attention/
dot_general:``) or wrapped by the transform that made the operation
(``jvp(ce_head)``, ``transpose(jvp(embed))``).

An operation belongs to a scope when any component of its ``tf_op``, bare or
unwrapped, is the scope's name; a fusion counts where its root does (XLA
gives a fusion its root's metadata); the one operation whose metadata XLA
replaces outright is mapped by hand (``RENAMED_BY_XLA``).  Times are self times
(``trace_reduce._self_times``: a ``while`` does not count its body twice)
inside the window ``trace_reduce.reduce`` uses, the first to the last step
annotation, as a mean over chips, in ms per step.

``what`` is ``scope_ms_per_step`` (with ``scope``), ``unscoped_pct`` (busy
self time under none of ``SCOPES``, the guard on the table),
``forward_ms_per_step`` (a ``jvp(`` and no ``transpose(`` in the name stack)
or ``backward_ms_per_step`` (a ``transpose(``).  A trace whose operations
name no scope at all (a program from before the scopes) reads nothing.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmark import trace_reduce, xplane_meta
from benchmark.harness import say

#: top-level scope -> inner scopes: the program's
#: ``telemetry.spans.DEVICE_SCOPES``, kept here as the yardstick's own copy
SCOPES = {
    "embed": (),
    "attention": ("flash_fwd", "flash_dq", "flash_dkv"),
    "mlp": (),
    "moe": ("router", "dispatch", "experts", "combine"),
    "ce_head": (),
    "grad_accum": (),
    "optimizer": ("clip", "adamw", "zero1_bucket_ag"),
}
#: XLA's TPU compiler rewrites ``lax.ragged_dot`` into a kernel of its own and
#: replaces the operation's metadata, name stack and all, with these names.
#: The program calls ``ragged_dot`` in one place, the experts of its
#: ``ops/moe.py``, so that is where their time is counted.
RENAMED_BY_XLA = {"ragged-dot-none": "moe/experts",
                  "ragged-dot-metadata": "moe/experts"}
WRAPPER = re.compile(r"^(?:jvp|transpose|checkpoint|remat)\((.*)\)$")
REMAT = ("checkpoint", "remat")


def components(tf_op: str) -> list[str]:
    """The name stack's components, each unwrapped from ``jvp(...)``,
    ``transpose(...)``, ``checkpoint(...)``, ``remat(...)`` (nested too)."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        out.append(part)
    return out


def scope_path(tf_op: str) -> Optional[str]:
    """``optimizer/adamw/zero1_bucket_ag``: the first top-level scope in the
    name stack and the inner scopes of it that follow; None without one."""
    if tf_op.rstrip(":") in RENAMED_BY_XLA:
        return RENAMED_BY_XLA[tf_op.rstrip(":")]
    top, path = None, []
    for part in components(tf_op):
        if top is None:
            if part in SCOPES:
                top, path = part, [part]
        elif part in SCOPES[top] and part not in path:
            path.append(part)
    return "/".join(path) if top else None


def phase(tf_op: str) -> str:
    """``backward`` (a ``transpose(`` in the name stack), ``forward`` (a
    ``jvp(`` and no ``transpose(``) or ``neither`` (accumulation, optimizer)."""
    if "transpose(" in tf_op:
        return "backward"
    return "forward" if "jvp(" in tf_op else "neither"


def under_remat(tf_op: str) -> bool:
    """A ``checkpoint`` or ``remat`` component, bare or as a wrapper."""
    return any(p.partition("(")[0] in REMAT
               for p in tf_op.rstrip(":").split("/"))


def window(raw: dict, step_name: str = "train"):
    """``(lo, hi, steps, loop thread's events)`` as ``trace_reduce.reduce``
    takes them; None without two step annotations."""
    marks, main = [], []
    for evs in raw["host"].values():
        found = sorted(s for n, s, _ in evs if n == step_name)
        if len(found) > len(marks):
            marks, main = found, evs
    if len(marks) < 2:
        return None
    return marks[0], marks[-1], len(marks) - 1, main


def reduce_scopes(path) -> Optional[dict]:
    """Self seconds per step, mean over chips: ``by_path`` (scope path or
    ``unscoped``), ``by_row`` ((scope path, opcode)), ``by_phase``,
    ``remat_backward``, ``collective`` by top-level scope, ``unscoped_ops``
    ((tf_op, opcode) of what no scope holds) and ``busy``."""
    raw = trace_reduce.load(path)
    win = window(raw)
    if not raw["chips"] or win is None:
        return None
    lo, hi, steps, _ = win
    tf = xplane_meta.tf_ops(path)
    n = len(raw["chips"]) * steps * 1e9
    out = {"by_path": {}, "by_row": {}, "by_phase": {}, "collective": {},
           "unscoped_ops": {}, "remat_backward": 0.0, "busy": 0.0}

    def add(table: dict, key, ns: float) -> None:
        table[key] = table.get(key, 0.0) + ns / n

    for ops in raw["chips"].values():
        clipped = [(name, *c) for name, a, b in ops
                   if (c := trace_reduce._clip((a, b), lo, hi)) is not None]
        for name, ns in trace_reduce._self_times(clipped):
            op = tf.get(name, "")
            short = trace_reduce.short_name(name)
            opcode = short.rpartition(" ")[2]
            where = scope_path(op) or "unscoped"
            which = phase(op)
            out["busy"] += ns / n
            add(out["by_path"], where, ns)
            add(out["by_row"], (where, opcode), ns)
            add(out["by_phase"], which, ns)
            if which == "backward" and under_remat(op):
                out["remat_backward"] += ns / n
            if trace_reduce.COLLECTIVE.search(short):
                add(out["collective"], where.partition("/")[0], ns)
            if where == "unscoped":
                add(out["unscoped_ops"], (op or "(no tf_op)", opcode), ns)
    return out


def _reduced(ctx) -> Optional[dict]:
    if "scope_trace" not in ctx:
        ctx["scope_trace"] = None
        xplane = trace_reduce.find_xplane(ctx["log_dir"] / "trace")
        if ctx.get("trace") is not None and xplane is not None:
            ctx["scope_trace"] = sc = reduce_scopes(xplane)
            if sc is not None and set(sc["by_path"]) - {"unscoped"}:
                for (where, opcode), s in sorted(
                        sc["by_row"].items(), key=lambda kv: -kv[1])[:10]:
                    say(f"scopes: {where} {opcode} {s * 1e3:.3f} ms a step")
                for top, s in sorted(sc["collective"].items()):
                    say(f"scopes: collectives under {top} {s * 1e3:.3f} ms a step")
    return ctx["scope_trace"]


def scope_seconds(sc: dict, scope: str) -> float:
    return sum(s for p, s in sc["by_path"].items()
               if p == scope or p.startswith(scope + "/"))


def read(ctx, *, what, scope=None):
    sc = _reduced(ctx)
    if sc is None:
        return None
    if what in ("forward_ms_per_step", "backward_ms_per_step"):
        if not sc["by_phase"].get("forward") or not sc["by_phase"].get("backward"):
            return None
        which = what.partition("_")[0]
        if which == "backward":
            say(f"scopes: backward {sc['by_phase']['backward'] * 1e3:.3f} ms a "
                f"step, {sc['remat_backward'] * 1e3:.3f} of it under checkpoint "
                f"(recomputed forward and its backward: the name stack does not "
                f"tell them apart); neither pass "
                f"{sc['by_phase'].get('neither', 0.0) * 1e3:.3f}")
        return 1e3 * sc["by_phase"][which]
    if not set(sc["by_path"]) - {"unscoped"}:
        return None  # no operation names a scope: nothing to read
    if what == "unscoped_pct":
        for (op, opcode), s in sorted(sc["unscoped_ops"].items(),
                                      key=lambda kv: -kv[1])[:5]:
            say(f"scopes: unscoped {opcode} {s * 1e3:.3f} ms a step: {op[-100:]}")
        return 100.0 * sc["by_path"].get("unscoped", 0.0) / sc["busy"]
    if what == "scope_ms_per_step":
        seconds = scope_seconds(sc, scope)
        for inner in SCOPES[scope]:
            part = sum(s for p, s in sc["by_path"].items()
                       if p.startswith(scope + "/") and p.endswith("/" + inner))
            say(f"scopes: {scope}/{inner} {part * 1e3:.3f} ms a step")
        return 1e3 * seconds if seconds > 0 else None
    raise ValueError(f"scope_time: unknown what={what!r}")
