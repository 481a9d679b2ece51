"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per device operation, collective time, and the longest idle gaps with
what the host was doing in them.  Read with ``jax.profiler.ProfileData``
alone.  ``tests/benchmark`` checks it on a small trace recorded on the chip
(``benchmark/data/``).

Layout of a TPU trace (one plane per chip, ``/device:TPU:<n>``): the line
``XLA Ops`` holds one event per executed HLO operation (Pallas kernels among
them), ``Steps`` and ``XLA Modules`` hold one event per program run.  Host
threads are lines of ``/host:CPU``; the trainer's ``StepTraceAnnotation``
shows there as events named ``train``.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)


def find_xplane(trace_dir: Path) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def _union(intervals: Iterable[tuple]) -> list[tuple]:
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: tuple, lo: float, hi: float) -> Optional[tuple]:
    a, b = max(ev[0], lo), min(ev[1], hi)
    return (a, b) if b > a else None


def load(path: Path) -> dict:
    """Planes of interest as plain lists: per chip ``(name, start, end)`` of
    every device operation, and per host thread every event, in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    chips: dict[int, list] = {}
    host: dict[str, list] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[int(m.group(1))] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
    return {"chips": chips, "host": host}


def describe(path: Path, top: int = 25) -> str:
    """What a trace holds, for a reader of one by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            tot: dict[str, float] = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {ns / 1e6:10.3f} ms  {name}")
    return "\n".join(out)


def short_name(name: str) -> str:
    """An XLA op's event name is its whole HLO line; keep the instruction's
    name and opcode: ``%fusion.231 = bf16[...] fusion(...)`` -> ``fusion.231 fusion``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def flash_kind(name: str):
    """Which flash kernel an ``XLA Ops`` event is, by the structure of its HLO
    line and not by its instruction name (which moves with every refactor): a
    ``custom-call`` whose first operand is a 4-D bf16 ``[b, heads, s, d]``
    tensor.  Forward returns ``(o, lse)`` with a float32 ``lse``; dq returns
    one array; dkv returns two.  None for any other operation."""
    head, sep, args = name.partition(" custom-call(")
    if not sep or not re.match(r"bf16\[\d+,\d+,\d+,\d+\]", args):
        return None
    result = head.partition(" = ")[2]
    if not result.startswith("("):
        return "dq"
    return "fwd" if "f32[" in result else "dkv"


def _self_times(ops: list) -> list:
    """``(name, self seconds in ns)``: an operation's time less the time of
    the operations nested in it (a ``while`` holds its body's operations)."""
    out, stack = [], []  # stack of [name, end, self]
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((n, s_) for n, _, s_ in stack)
    return out


#: what the host's main thread was doing, by the profiler's python tracer
HOST_SPANS = {
    "host_sync": r"__float__|np\.asarray\(jax\.Array\)|block_until_ready",
    "data_wait": r"loader\.py:\d+ __next__|queue\.py:\d+ get",
    "log_metrics": r"exp_manager\.py:\d+ log_metrics",
    "dispatch": r"LoadedExecutable.*Execute|pxla\.py:\d+ __call__",
}


def reduce(path: Path, *, step_name: str = "train",
           host_spans: Optional[dict] = None) -> Optional[dict]:
    """The window is from the first step annotation to the last one in the
    trace: whole step periods, host gaps between steps included.  Without
    annotations it is the extent of the device operations (and ``steps`` is
    None).  Returns None when no operation ran on a device."""
    raw = load(path)
    if not raw["chips"] or not any(raw["chips"].values()):
        return None
    marks, main = [], []
    for evs in raw["host"].values():
        found = sorted(s for n, s, _ in evs if n == step_name)
        if len(found) > len(marks):
            marks, main = found, evs  # the thread that runs the loop
    if len(marks) >= 2:
        lo, hi, steps = marks[0], marks[-1], len(marks) - 1
    else:
        evs = [e for ops in raw["chips"].values() for e in ops]
        lo, hi, steps = min(e[1] for e in evs), max(e[2] for e in evs), None
    window = hi - lo
    busy, per_op, flash, coll, gaps = [], {}, {}, [], []
    n = len(raw["chips"])
    for chip, ops in sorted(raw["chips"].items()):
        clipped = [(name, *c) for name, a, b in ops
                   if (c := _clip((a, b), lo, hi)) is not None]
        merged = _union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, self_ns in _self_times(clipped):
            per_op[name] = per_op.get(name, 0.0) + self_ns / n
        for name, a, b in clipped:
            kind = flash_kind(name)
            if kind:
                flash[kind] = flash.get(kind, 0.0) + (b - a) / n
        coll.append(sum(b - a for a, b in _union(
            (a, b) for name, a, b in clipped if COLLECTIVE.search(short_name(name)))))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    spans = {k: re.compile(v) for k, v in (host_spans or HOST_SPANS).items()}

    def covering(a: float, b: float) -> str:
        best, share = "other", 0.0
        for label, rx in spans.items():
            cov = sum(bb - aa for aa, bb in _union(
                (max(a, s_), min(b, e)) for nme, s_, e in main
                if min(b, e) > max(a, s_) and rx.search(nme)))
            if cov > share:
                best, share = label, cov
        return best

    gaps.sort(reverse=True)
    short: dict[str, float] = {}
    for name, ns in per_op.items():
        key = short_name(name)
        short[key] = short.get(key, 0.0) + ns / 1e9
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "steps": steps,
        "chips": n,
        "collective_s": sum(coll) / n / 1e9,
        "flash_s": {k: v / 1e9 for k, v in flash.items()},
        "op_seconds": short,
        "idle_gaps": [[covering(a, b), g / 1e9] for g, a, b in gaps[:10]],
    }
