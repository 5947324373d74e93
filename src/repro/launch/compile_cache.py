"""JAX's persistent compilation cache, set in one place.

``enable_compile_cache()`` is called first thing by the entry points
(``chip_smoke.py`` and the ``main()`` of simulate, train and sweep_run),
never on import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads its cache from there and nothing else is set. Otherwise the cache
goes to the fixed ``<checkout>/.jax_cache`` (git-ignored): the directory
is part of the cache key, so a path built from a temp name, a pid or the
time would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
