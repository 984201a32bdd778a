"""Persistent compilation cache shared by every entry point.

Compiling the serving step of a full-width model takes tens of seconds per
program; JAX's persistent cache lets a later process reuse the result.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory in the checkout: a cache that moves is never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is ``.jax_cache/`` at
    the checkout root.  On the CPU backend nothing is set: its compiles are
    short, and XLA:CPU warns on every executable it reloads.  Call before
    the first compile (this initializes the backend, so after any
    ``XLA_FLAGS`` device forcing).
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
