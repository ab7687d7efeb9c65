"""One place that turns on JAX's persistent compilation cache.

The cache directory is part of the cache key's lookup, so it must not move
between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no directory is set in code; otherwise the cache lives at a
fixed path inside the checkout (``.cache/`` is git-ignored)."""

from __future__ import annotations

import os
from typing import Optional

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache(default_dir: Optional[str] = None) -> str:
    """Enable the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``default_dir``, else
    ``<checkout>/.cache/jax``. Call before the first compile."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = default_dir or DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir
