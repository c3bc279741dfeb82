"""Placement of JAX's persistent compilation cache for entry points.

Called by ``chip_smoke.py`` and the ``examples/`` and ``benchmarks/``
scripts before they compile anything; never on import of a library
module and never by the tests.  The cache is keyed partly on its own
path, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself, and this leaves it
alone), else ``<repo>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
