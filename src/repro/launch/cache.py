"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the fixed
``artifacts/jax_cache`` of this checkout (ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"


def use_compile_cache() -> None:
    """Point the persistent compilation cache at its fixed place.

    Call before the first compile.  Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that variable itself.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
