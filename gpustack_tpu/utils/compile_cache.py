"""One persistent XLA compile cache for every process that compiles.

Call :func:`enable_compile_cache` once, before the first jit, in every
process that compiles: the engine servers, ``__graft_entry__.py`` and
the test session. The directory is part of
what a later process has to find again, so it never moves:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
  directory is set in code;
- otherwise it is ``<checkout>/.jax_cache``, derived from this package's
  own location.

Engine subprocesses inherit the worker's environment, and the helper
runs in the child too, so a worker and all of its engines share one
cache.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: gpustack_tpu/utils/compile_cache.py -> two up
# from the package directory's parent.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # keep tiny programs too: a cold engine start is mostly many small
    # compiles, and the CPU test suite is nothing else
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
