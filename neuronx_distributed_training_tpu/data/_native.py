"""Compile-on-demand ctypes loading for the native (C++) data helpers.

One place for the pattern both ``data/megatron/index.py`` and
``data/packing.py`` need: rebuild the ``.so`` when the source is newer,
compile to a per-pid temp file and ``os.replace`` into place (concurrent
dataloader workers racing one output path can otherwise leave a corrupt
library whose fresh mtime pins the numpy fallback forever), and return
``None`` — never raise — when no toolchain is available.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)


def compile_and_load(src: Path) -> Optional[ctypes.CDLL]:
    """Build ``src`` (.cpp) into a sibling ``.so`` if stale, and load it."""
    lib_path = src.with_suffix(".so")
    try:
        if not lib_path.exists() or lib_path.stat().st_mtime < src.stat().st_mtime:
            tmp = lib_path.with_suffix(f".{os.getpid()}.tmp.so")
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", str(src), "-o", str(tmp)],
                check=True, capture_output=True,
            )
            os.replace(tmp, lib_path)
        return ctypes.CDLL(str(lib_path))
    except Exception as e:  # noqa: BLE001 — the numpy fallback is always correct
        # host-side and exact either way, but slower: say so, once per helper
        # (callers cache the handle, so this runs once per source file)
        logger.warning("native helper %s did not build (%s: %s); the numpy "
                       "index builder takes over", src.name,
                       type(e).__name__, e)
        return None
