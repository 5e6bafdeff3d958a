"""JAX's persistent compilation cache, at one fixed place.

A compiled step of a full-width model takes tens of seconds to build; the
cache lets the next process (or the next run) load it instead. The
directory is part of the cache's key, so it never moves: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored). Entry points call :func:`enable` before they compile.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
