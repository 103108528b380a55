"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``repro.launch`` CLIs and the
benchmark mains) call ``use_compile_cache()`` before their first compile,
so that repeated runs from one checkout reuse compiled programs.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/...
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and no
    other directory is set.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, never one made from a temporary name, a pid or the time,
    so that a later run from the same checkout finds its entries."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
