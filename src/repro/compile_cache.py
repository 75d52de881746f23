"""Where the entry points keep JAX's persistent compilation cache.

The solver compiles one fused step per working-set bucket and one kernel per
shape; a warm cache turns those compiles into reads. Importing ``repro``
sets nothing: the scripts that drive a run (``chip_smoke.py``,
``benchmarks/bench_engine.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    this sets nothing. Otherwise the cache goes to ``<checkout>/.jax-cache``,
    a fixed path (found from this file), so a second run in the same
    checkout finds the first run's compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
