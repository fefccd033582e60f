"""JAX's persistent compilation cache, at one place for every entry point.

A cache entry is found again only where it was written, so the directory
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that
variable itself, and no other directory is set here), otherwise
``<checkout>/.jax_cache`` — never a temporary, per-process or per-run
path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
