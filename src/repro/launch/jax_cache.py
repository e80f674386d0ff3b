"""Where entry points keep JAX's persistent compilation cache.

Called from ``launch/mine.py`` and ``chip_smoke.py`` — never on library
import, so tests and library callers keep whatever their process set.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
changes nothing.  Otherwise the cache goes to a fixed
``<checkout>/.jax_cache``: the directory is part of what a later run
must find, so a temp, pid- or time-derived path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = CHECKOUT / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
