"""JAX's persistent compilation cache: the one place that chooses its
directory.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the directory is part of each entry's key, so
a path that moves never hits.
"""
from __future__ import annotations

import os

#: the checkout root (this file is ``<root>/src/repro/util/...``)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
