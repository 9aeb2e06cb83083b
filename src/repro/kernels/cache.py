"""JAX's persistent compilation cache, for the processes that own a chip.

Every cold process would otherwise recompile every decode bucket shape.
Entry points call :func:`use_compile_cache` from their ``main()``; importing
this module touches neither jax nor the cache.
"""

from __future__ import annotations

import os

#: the cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: directory of the checkout, so that the next process finds it again
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    and no other directory is set; otherwise it goes to :data:`CACHE_DIR`.
    Kernel compiles are short, so every compile is cached however quick.
    Returns None, and does nothing, where jax is not installed or
    ``REPRO_NO_JAX`` opts out of it.
    """
    if os.environ.get("REPRO_NO_JAX"):
        return None
    try:
        import jax
    except ModuleNotFoundError:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
