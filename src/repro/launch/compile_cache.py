"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``)
call :func:`enable_compile_cache` once at start; importing this module sets
nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
no other directory is configured.  Otherwise the cache is ``.jax_cache/`` at
the repository root: a fixed path, because the path is part of what a later
run must find again.  Either way every compiled program is kept: the
engine's step programs compile in under a second each on a v5e, below
JAX's default threshold for persisting, and there are dozens of them.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
