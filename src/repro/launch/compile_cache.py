"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so the directory must not
move between runs: it is never built from a temporary name, a PID or
the time.
"""
from __future__ import annotations

import os

#: the checkout's own cache directory (listed in .gitignore)
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing else is set; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
