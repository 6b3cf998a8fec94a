"""Where JAX keeps its persistent compilation cache for this repository.

A cache hit needs the same directory every time: the path is part of what
a later run looks up.  ``JAX_COMPILATION_CACHE_DIR``, when the
environment sets it, is the cache.  Otherwise the cache is
``.jax_cache/`` at the root of the checkout — fixed, inside the
checkout, and listed in ``.gitignore``.  The entry points (``chip_smoke.py`` and the ERA
``launch`` drivers) call :func:`use_compile_cache` once at start-up.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
