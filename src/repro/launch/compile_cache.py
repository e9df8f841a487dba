"""JAX persistent compilation cache for the entry points that run on a
chip (``chip_smoke.py``, ``examples/train_mc.py``, ``launch/serve_mc.py``).

A compile at Netflix widths takes long enough that a second process
should find it on disk.  The cache key includes the directory, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads
that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  Tests never call
this.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache``: this file is <checkout>/src/repro/launch/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
