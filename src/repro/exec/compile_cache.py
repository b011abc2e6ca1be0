"""JAX's persistent compilation cache, placed from outside.

Every planned GF op compiles one executable per (op, shape bucket), so a
cold process on the chip spends much of its start-up compiling.  Entry
points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile; importing
``repro`` never does, so tests keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
cache stays there.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the directory is part of the
cache key and a path that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Thresholds are dropped to zero so that the plan cache's small,
    quick-to-compile executables are written too: each one is cheap,
    but there are dozens per process.

    MLIR locations keep only the innermost frame.  A Pallas kernel carries
    its locations into the cache key (the kernel body is serialized into
    the custom call), so with the whole traceback in them the same kernel
    traced from another call site, or after an edit to any caller, misses.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path


__all__ = ["enable_compile_cache", "DEFAULT_DIR", "ENV_VAR"]
