"""JAX's persistent compilation cache for the entry points.

Entry points (launch/train.py, launch/serve.py, chip_smoke.py) call
enable_compile_cache() before their first compile; importing repro never
does. Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and
nothing else is set here. Otherwise the cache lives at a fixed
<checkout>/.jax_cache: the directory is part of the cache key, so a path
built from a temp name, a pid or the time would never hit again.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
