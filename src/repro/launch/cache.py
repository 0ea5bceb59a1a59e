"""Persistent compilation cache for the entry points.

Called from a ``main`` (``chip_smoke.py``, ``launch.train``,
``launch.serve``), never while a module is imported and never in tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# A fixed path inside the checkout: a directory named from a temporary
# name, a pid or the time would start empty on every run and never hit.
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)
