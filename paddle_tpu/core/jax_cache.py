"""Where JAX's persistent compilation cache lives for on-chip entry points.

One place decides, so `chip_smoke.py`, the benches and the `tools/*bench`
mains agree: a cache directory is part of the cache key, and a directory
that moves between processes never hits.  (The repo's own AOT export cache
— `compile_cache_dir` flag, static/compile_cache.py — is a separate
mechanism and stays off by default.)
"""
from __future__ import annotations

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set nothing is touched (JAX reads
    the variable itself, and whoever set it owns the placement).  Otherwise
    the cache is ``<checkout>/.jax_cache`` — a fixed, git-ignored path
    inside the tree, never built from a temp dir, a pid or a timestamp."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
