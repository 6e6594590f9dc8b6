"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so the path must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself and nothing here overrides it), and otherwise ``.jax_cache/``
at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`;
    returns the directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
