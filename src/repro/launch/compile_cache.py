"""JAX's persistent compilation cache, placed from outside the program.

Call :func:`enable_compile_cache` before the first compilation.  A
``JAX_COMPILATION_CACHE_DIR`` in the environment wins (JAX reads it
itself); otherwise the cache lives at a fixed ``.jax_cache/`` at the root
of the checkout, so a later process finds what an earlier one compiled.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every program is cached, however fast it compiled: serving compiles
    one small program per (window, candidate-bucket) shape."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
