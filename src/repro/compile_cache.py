"""Where JAX keeps compiled programs between processes of this repo.

Entry points that compile large programs (``chip_smoke.py``, the
benchmarks) call :func:`use_compile_cache` once at start-up; importing
``repro`` never touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the cache key includes it, so a directory that moved
# (a temp name, a pid, a time stamp) would never hit.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
