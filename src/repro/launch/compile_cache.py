"""JAX's persistent compilation cache, at one fixed place.

A compiled program is keyed on, among other things, the cache directory's
path, so a directory that moves between runs never hits.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives in ``<checkout>/.jax_cache``
(git-ignored).  Entry points call :func:`enable_compile_cache` from their
``main()``; importing a module never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
