"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is configured.  Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, ignored by git): the path is
part of the cache key, so a directory that moved would never hit.

Call :func:`configure_compile_cache` from a ``main()``, before the first
compile — never at import, so importing the package (tests) leaves JAX's
configuration alone.
"""

from __future__ import annotations

import os

import jax

__all__ = ["configure_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def configure_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: a cold chip machine recompiles whatever is missing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
