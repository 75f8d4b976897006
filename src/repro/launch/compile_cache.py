"""Where JAX keeps its persistent compilation cache.

Each entry point calls ``enable()`` from its ``main()``, before its first
compile, and never at import.  A ``JAX_COMPILATION_CACHE_DIR`` set in the
environment wins and nothing else is touched: JAX reads it itself.
Otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path, so
that a later run from the same checkout finds what an earlier one
compiled (the path is part of the cache key).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
