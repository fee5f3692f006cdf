"""JAX's persistent compilation cache for this repository's entry points.

``enable_compile_cache()`` is the first call in the ``main`` of every entry
point (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``, the
benchmark scripts); nothing calls it on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the helper
sets no other directory.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (git-ignored): the directory is part of what a
cached entry is found by, so it never comes from a temporary name, a pid
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
