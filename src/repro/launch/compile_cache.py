"""JAX's persistent compilation cache for the entry points.

Only the launchers (``repro.launch.serve``, ``repro.launch.train``) and
``chip_smoke.py`` call :func:`enable_compile_cache`, before their first
compile; importing the library or running the tests never turns it on.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root. The cache path
# is part of the cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
