"""Persistent XLA compilation cache shared by the entry points."""
from __future__ import annotations

import os
from pathlib import Path

# the checkout root: src/repro/launch/cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX already reads it, and
    no other directory is set).  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the path is part of what a later process
    looks up, so it must not move between runs.  Entry points call this
    from ``main``; importing the package never does.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
