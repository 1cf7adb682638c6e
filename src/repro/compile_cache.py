"""JAX's persistent compilation cache, turned on by the entry points.

The simulator compiles one program per (layer, strip) on its jitted
trace path and one Pallas kernel per distinct chunk shape, so a cold
ImageNet run compiles a few hundred small programs; the cache keeps
them across runs.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, the ``examples/`` scripts) call
:func:`enable_compile_cache`; importing the package never does, so the
tests run without a persistent cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the default cache: a fixed path inside the checkout (git-ignored) —
#: the directory is part of what a cache entry is found by, so it never
#: depends on a temporary name, a process id or the time
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at :data:`CACHE_DIR`
    and keeps every program, however fast it compiled (most of the
    simulator's compile in well under JAX's default one-second floor).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)
