"""JAX's persistent compilation cache, placed the same way for every
entry point.

``enable_compile_cache()`` is called by the programs a user starts -
``python -m repro.launch.serve``, ``chip_smoke.py`` and the benchmark
scripts' ``__main__`` blocks - and never while a module is imported.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
the cache lives there; otherwise it lives in ``<repo>/.jax-cache``.  A
fixed path matters: the directory is part of a cached program's key, so
a cache that moves between runs never hits.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax-cache"))


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax-cache``."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()`` and cache
    every program (a serving run compiles many small ones, each of
    which would otherwise fall under JAX's size and time thresholds).
    Returns the directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
