"""Persistent XLA compilation cache for the entry points.

Only entry points call this (never ``import repro``), so library users and
the tests keep JAX's defaults.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(repo_root: str) -> str:
    """Cache compiled programs in ``$JAX_COMPILATION_CACHE_DIR`` when it is
    set (JAX reads it itself), else in the fixed ``<repo_root>/.jax_cache``;
    returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(repo_root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
