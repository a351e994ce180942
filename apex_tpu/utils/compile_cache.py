"""Persistent XLA compile cache with a placeable, fixed location.

Every entry point that compiles for the chip (``chip_smoke.py``,
``python -m cellbench.run``, ``python -m apex_tpu.loadtest``) calls :func:`enable_compile_cache` first. The
directory is part of the cache key's world — a cache that moves never
hits — so it is either the one the environment names or ONE fixed path
inside the checkout, never a temporary directory.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — derived from this file's location
#: (``<checkout>/apex_tpu/utils/compile_cache.py``); git-ignored
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already reads it and this
    sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
    return _IN_CHECKOUT
