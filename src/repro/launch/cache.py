"""JAX's persistent compilation cache, placed from outside.

A process that runs the monitor on the chip compiles the same kernels
and jitted steps on every start; the persistent cache lets the next
process on the same machine load them instead.  The cache's path is part
of its key, so it must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
when the deployment sets it (JAX reads the variable itself), otherwise a
fixed directory in the checkout — never a temporary, per-process or
time-stamped one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Leaves ``JAX_COMPILATION_CACHE_DIR`` to JAX when it is set and sets
    no other directory; otherwise uses :data:`CHECKOUT_CACHE`.  Caches
    every compile, however short: the Pallas kernels compile in well
    under JAX's default one-second threshold.  Call before the first
    compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
