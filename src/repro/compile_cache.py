"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every entry point (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before it compiles
anything, so a second run of the same programs loads them instead of
compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# build/ is git-ignored. The path is fixed on purpose: a directory whose name
# changes from run to run would never be found again.
FIXED_CACHE_DIR = Path(__file__).resolve().parents[2] / "build" / "jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself; otherwise the cache lives in ``build/jax_cache`` of the checkout.
    Call it before the first compilation: JAX fixes the cache's place when
    it first compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(FIXED_CACHE_DIR))
    return str(FIXED_CACHE_DIR)
