"""Where the launchers keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, and
nothing here overrides it. Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout — a fixed path, because the directory is part of
what a later run has to find again.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache",
)


def cache_dir() -> str:
    """The directory compiled programs persist in."""
    return os.environ.get(ENV) or CHECKOUT_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on (call before the first compile) and
    return its directory."""
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return cache_dir()
