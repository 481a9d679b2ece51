"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (``nxdt-train``, ``chip_smoke.py``,
``benchmark/run.py``): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no directory is set in code; where it is not, the cache sits at one
fixed path inside the checkout.  The path is part of the cache key, so a
temporary or per-process directory would never hit."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: the fixed in-checkout default (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compilation_cache(explicit: Optional[str] = None) -> str:
    """Place the persistent compilation cache and return its directory.
    ``explicit`` (``nxdt-train --compilation-cache``) overrides both the
    environment and the default."""
    import jax

    if explicit:
        jax.config.update("jax_compilation_cache_dir", explicit)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return str(jax.config.jax_compilation_cache_dir)
