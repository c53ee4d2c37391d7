"""JAX's persistent compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory and
nothing else is configured in code.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout root: the path is part of the cache key,
so it must not move between runs (no temporary, pid- or time-based name).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_ROOT", "compile_cache_dir", "enable_compile_cache"]

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(environ=os.environ) -> Path:
    """The directory the cache uses under ``environ``."""
    return Path(environ[_ENV]) if environ.get(_ENV) else \
        CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
