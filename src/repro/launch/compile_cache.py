"""Persistent compilation cache for the entry scripts (``chip_smoke.py``,
``benchmarks/run.py``).

Call :func:`enable` once at the start of a run, before the first compile;
importing this module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and this sets nothing. Otherwise the cache goes to
``.jax_cache/`` at the checkout root (git-ignored). The path is fixed — never
derived from a temporary name, a pid or the time — because JAX keys its
entries on it, and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory it writes to."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
