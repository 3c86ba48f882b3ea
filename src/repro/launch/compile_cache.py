"""JAX's persistent compilation cache, for the entry-point scripts.

Called by entry points only (``chip_smoke.py``, the examples' and the
serving CLI's ``main``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(listed in ``.gitignore``): the path is part of the cache key, so a
directory that moves between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
