"""JAX's persistent compilation cache for the entry points.

A full-width step compiles for tens of seconds; with the cache on, a
second run of the same program loads the executable instead.  The
entry points (``repro.launch.train``, ``repro.launch.serve`` and
``chip_smoke.py``) call :func:`enable_compile_cache` at start-up, before
their first compile — never at import, so tests and library users keep
JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache — a fixed path: the cache is only found again by a
# run that looks in the same directory
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX
    itself and is the only cache directory used; otherwise the cache
    lives in :data:`DEFAULT_DIR`."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR


def cache_entries(path: Path) -> int:
    """Number of entries in a cache directory (0 when it is absent)."""
    return sum(1 for _ in path.iterdir()) if path.is_dir() else 0
