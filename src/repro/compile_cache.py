"""Persistent XLA compilation cache for the entry points.

Scripts (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
:func:`enable_compile_cache` once before their first compile; the library
never calls it on import. The cache directory is part of the cache's key,
so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  here overrides it.
* otherwise: ``<checkout>/.jax_cache`` (git-ignored), one fixed path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache(root: str | os.PathLike | None = None) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``root``: the checkout whose ``.jax_cache`` holds the cache when
    ``JAX_COMPILATION_CACHE_DIR`` is unset (default: this package's
    checkout).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root or CHECKOUT).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
