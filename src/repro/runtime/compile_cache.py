"""JAX's persistent compilation cache, at one fixed place.

Compiling the front kernels takes seconds per shape class on a TPU, so
entry points that drive the chip (``chip_smoke.py``, ``benchmarks.run``)
keep compiled programs across processes.  The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else ``.jax_cache`` at
the root of the checkout: a fixed path, since the path is part of what a
later process looks the cache up by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
