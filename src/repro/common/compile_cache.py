"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` from their ``main`` — never at
import, so processes that only import the package (tests among them) get
no cache from it.
"""
from __future__ import annotations

import os

import jax

#: Root of the checkout this package is loaded from (``src/repro/common``
#: is three levels below it).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Return the directory JAX caches compiled programs in.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    a fixed path, since the directory is part of what a later run must
    find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
