"""Crash-safe file I/O helpers.

``run_summary.json`` / ``trace_summary.json`` / ``fleet_summary.json`` are
read by resume paths, report tools and the benchmark's readers — a
SIGKILL landing mid-write (preemption, OOM-killer, the elastic drill's kill
injector) must never leave a truncated JSON document for them to choke on.
``atomic_write_json`` serializes FIRST (an unserializable value raises
before the target is touched), writes a same-directory temp file, fsyncs,
and renames into place — the POSIX whole-file-or-nothing pattern.  Remote
object stores (``gs://`` …) commit whole objects by construction, so those
paths take a single ``epath`` write instead.
"""

from __future__ import annotations

import json
import os
from typing import Any


def atomic_write_json(path: Any, obj: Any, *, indent: int = 1,
                      sort_keys: bool = True) -> None:
    """Write ``obj`` as JSON to ``path`` atomically (temp + rename).

    The serialization happens up front: a non-serializable ``obj`` raises
    ``TypeError`` with the TARGET FILE UNTOUCHED — the old contents stay
    valid, which is the whole point.
    """
    data = json.dumps(obj, indent=indent, sort_keys=sort_keys) + "\n"
    spath = str(path)
    if "://" in spath:
        from etils import epath

        p = epath.Path(spath)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(data)  # object stores commit whole objects
        return
    tmp = f"{spath}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:  # pragma: no cover — some filesystems refuse
            pass
    os.replace(tmp, spath)
