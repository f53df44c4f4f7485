"""Persistent XLA compile cache for the entry points.

Call ``configure()`` once at the start of a program (never on import).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here.  Otherwise the cache lives at a fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of every entry's key, so it never depends on a temporary name, a
process id or the time.  Every program is cached, however quick its
compile, so a second run of the same shapes pays no XLA compile at all.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def configure() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    cache_dir = os.environ.get(ENV)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
