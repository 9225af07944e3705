"""JAX's persistent compilation cache for the repo's entry points.

A run finds earlier compiles only in the directory they were written to,
so the directory must not move between runs: it is
JAX_COMPILATION_CACHE_DIR when that is set (JAX reads the variable itself)
and otherwise the fixed `<repo>/.jax_cache`, which .gitignore lists.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
