"""JAX's persistent compilation cache for the entry points.

Entry points (``repro.launch.serve``, ``chip_smoke.py``) call
:func:`enable_compilation_cache` once before they compile anything; nothing
calls it at import, and tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, git-ignored directory in the checkout: the cache is keyed by path,
# so a directory that moved between runs would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
