"""The part of an ``.xplane.pb`` that ``jax.profiler.ProfileData`` does not
show: the stats of a plane's *event metadata*.  On a TPU plane every ``XLA
Ops`` event's metadata carries ``tf_op``, JAX's name stack for the operation
(``jit(train_step)/while/body/closed_call/jvp(ce_head)/dot_general:``), which
is where the program's ``jax.named_scope`` names arrive.

Read from the protobuf wire format with the standard library alone
(tensorflow's ``xplane_pb2`` is not imported).  Field numbers, from
``xplane.proto``: XSpace.planes=1; XPlane name=2, event_metadata=4,
stat_metadata=5 (both maps: key=1, value=2); XEventMetadata name=2, stats=5;
XStat metadata_id=1, str_value=5, ref_value=7; XStatMetadata id=1, name=2.
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterator

from benchmark.trace_reduce import DEVICE_PLANE

STAT = "tf_op"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[tuple[int, int, object]]:
    """``(field number, wire type, value)``: a varint's int, or the bytes of
    a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield field, wire, value


def _map_values(entries: list[bytes]) -> Iterator[bytes]:
    for entry in entries:
        for field, wire, value in _fields(entry):
            if field == 2 and wire == 2:
                yield value


def _plane_ops(plane: bytes) -> tuple[str, dict[str, str]]:
    name, events, stats = "", [], []
    for field, wire, value in _fields(plane):
        if wire != 2:
            continue
        if field == 2:
            name = value.decode()
        elif field == 4:
            events.append(value)
        elif field == 5:
            stats.append(value)
    if not DEVICE_PLANE.match(name):
        return name, {}
    stat_names: dict[int, str] = {}
    for meta in _map_values(stats):
        sid, sname = 0, ""
        for field, wire, value in _fields(meta):
            if field == 1 and wire == 0:
                sid = value
            elif field == 2 and wire == 2:
                sname = value.decode()
        stat_names[sid] = sname
    ops: dict[str, str] = {}
    for meta in _map_values(events):
        ev_name, found = "", None
        for field, wire, value in _fields(meta):
            if field == 2 and wire == 2:
                ev_name = value.decode()
            elif field == 5 and wire == 2:
                sid, text = 0, None
                for f, w, v in _fields(value):
                    if f == 1 and w == 0:
                        sid = v
                    elif f == 5 and w == 2:
                        text = v.decode()
                    elif f == 7 and w == 0:
                        text = stat_names.get(v)
                if stat_names.get(sid) == STAT and text is not None:
                    found = text
        if found is not None:
            ops[ev_name] = found
    return name, ops


def tf_ops(path: Path) -> dict[str, str]:
    """``{event name: tf_op}`` over the device planes (chips run one program:
    an event name means the same operation on each)."""
    out: dict[str, str] = {}
    for field, wire, plane in _fields(Path(path).read_bytes()):
        if field == 1 and wire == 2:
            out.update(_plane_ops(plane)[1])
    return out
