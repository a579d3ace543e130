"""JAX's persistent compilation cache for the entry points.

A cold chip run recompiles a 32-layer model at every bucket size, so the
launchers and ``chip_smoke.py`` keep compiled programs on disk.  Called
from their ``main()``, never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# fixed, inside the checkout: a directory named from a temp name, a pid
# or the time would be a new, empty cache on every run
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here; otherwise the cache goes to ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
