"""JAX's persistent compilation cache at one fixed place per checkout.

The cache key includes the directory, so the directory must never move:
a fixed ``<repo root>/.jax_cache`` lets a second run of the same program in
the same checkout reuse every executable the first one compiled. Where the
environment names a cache (``JAX_COMPILATION_CACHE_DIR``), JAX reads that
itself and this module sets nothing. Tests never call ``enable``: their
compiles stay out of any cache.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/compile_cache.py -> parents[2]
REPO_ROOT = Path(__file__).resolve().parents[2]


def cache_dir_to_set() -> Optional[Path]:
    """The directory ``enable`` would configure: None when the environment
    already names one (JAX applies it unaided), else ``<repo>/.jax_cache``."""
    if os.environ.get(ENV_VAR):
        return None
    return REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir_to_set()
    if path is None:
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
