"""JAX's persistent compilation cache, switched on the same way by every
entry point (``chip_smoke.py`` and the ``repro.launch`` CLIs).

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that
directory is the cache and nothing here names another.  Otherwise the cache
is ``.jax_cache/`` at the checkout root.  The path is fixed (never built
from a temp name, a pid or the time) because it is part of what a later
process must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
