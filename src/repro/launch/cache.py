"""JAX's persistent compilation cache for the entry points.

Called from each entry point's ``main()`` (never at import):
``launch/train.py``, ``launch/serve_population.py`` and ``chip_smoke.py``.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set in code; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (git-ignored).  The directory is part of the
cache key, so it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
