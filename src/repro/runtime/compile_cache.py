"""JAX's persistent compilation cache, at one fixed place.

A chip run may have to compile every program, and compiling is a large part
of a cold run. Processes that share the cache compile each program once,
but only if they look in the same place, so the directory never moves: it
is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself),
and otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache lives in: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache`` - the same path on every call."""
    return os.environ.get(ENV) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    the directory. Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    uses it and no other directory is set here. Call before the first
    compile."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
