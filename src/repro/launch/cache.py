"""JAX's persistent compile cache, placed for the entry points.

Called by ``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` at
start-up, never at import: a library that moved the cache would move it
for every program that imports it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so a later run of the same checkout finds what an earlier one
# wrote (the directory is part of the cache's key); listed in .gitignore
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
