"""Placement of JAX's persistent compile cache for the entry points.

The served episodes compile once per (method x bucket), a large share of
any cold start.  The cache only hits when it sits at the same path from
run to run, so the directory comes from ``JAX_COMPILATION_CACHE_DIR`` when
the deployment sets it (JAX reads that variable itself) and is otherwise
one fixed directory inside the checkout — never from tmp, a pid or the
clock.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return it.
    Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
