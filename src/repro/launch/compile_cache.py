"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once before
their first compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  else is configured here.
* otherwise: the cache is ``.jax_cache/`` at the root of the checkout
  that holds this package (listed in ``.gitignore``). The path is
  fixed, because it is part of the cache key: a second run from the
  same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_NAME = ".jax_cache"


def _checkout_root() -> Optional[Path]:
    """The checkout this package runs from (``src/repro/..`` holding
    ``pyproject.toml``), or None for an installed package."""
    root = Path(__file__).resolve().parents[3]
    return root if (root / "pyproject.toml").is_file() else None


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent cache; returns its directory (None when
    there is no checkout to hold it)."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    root = _checkout_root()
    if root is None:
        return None
    import jax
    path = str(root / CACHE_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
