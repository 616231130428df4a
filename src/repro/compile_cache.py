"""JAX persistent compile cache for the program's entry points.

Called by ``chip_smoke.py`` and the benchmark CLIs before their first
compile — never when a library module is imported.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and no
other is set; otherwise the cache lives at a fixed ``.jax_cache/`` in the
checkout root (the path is part of the cache key, so it must not move).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Default cache directory: ``.jax_cache/`` at the checkout root.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
