"""JAX persistent compilation cache placement for the entry-point scripts.

Call ``use_compile_cache`` from a script's ``main`` before its first
compile; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and no other directory is configured. Otherwise the cache goes to a fixed
directory inside the checkout: the path is part of the cache key, so a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root) -> str:
    """Turn the persistent cache on at ``$JAX_COMPILATION_CACHE_DIR``, or
    at ``<root>/.jax_cache`` when that is unset; returns the directory.
    The store's kernels compile in about a second each, so every compile
    is cached, however short."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
