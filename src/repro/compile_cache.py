"""JAX's persistent compilation cache, kept at one fixed place.

A kernel compiles once per (plan, rows bucket); with the cache on disk a
restarted process finds its kernels instead of compiling them again.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call ``enable_compile_cache`` once, before their first launch.  Tests do
not: a compile for a described (not attached) chip would be written to
the cache and could not be read back.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Turn the persistent compilation cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself,
    so no other directory is set here); otherwise ``.jax_cache/`` at the
    root of the checkout — a fixed path, since the directory is part of
    what makes an entry found again.  Every compile is kept: the kernels
    compile in about a second, under JAX's default one-second floor.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = pathlib.Path(env) if env else DEFAULT_DIR
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
