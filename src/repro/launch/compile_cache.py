"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed by, among other things, the cache directory, so
the directory must be the same in every run: ``$JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself, and nothing here overrides it), else
``.jax_cache/`` at the root of the checkout — never a temporary or
per-process name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one fixed place
    and return that directory. Call before the first compilation."""
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
