"""JAX persistent compilation cache for the entry points.

Called from ``main()`` of ``chip_smoke.py`` and the launch/benchmark
scripts — never at import, so tests and compile rehearsals stay cache-free.
The cache key includes its directory, so the path is fixed: a moving
directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here; otherwise the cache sits at ``.jax_cache/`` in
    the checkout (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
