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
    this sets no directory. Otherwise the cache goes to
    ``<checkout>/.jax-cache``, a fixed path (found from this file), so a
    second run in the same checkout finds the first run's compiles.

    The cache key includes the ops' names (``op_name``: the jit, scope and
    primitive of each op): by default JAX leaves metadata out, so a program
    whose stage scopes differ would read another program's executable, and
    a profile would show the other program's op names. Source frames are
    left out of the metadata, so the key does not move with a source line,
    a caller or the checkout's path.
    """
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
