"""JAX's persistent compilation cache, placed for the program's entry points.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
call `enable` once before they compile anything.  It is never called at
import: the compile-only tests build executables for a described chip that
no cache here could read back.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path in the checkout: the cache key includes the directory, so a
# path made from a temp name, a pid or the time would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here; otherwise the cache lives at
    `REPO_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
