"""Where this checkout keeps what it compiles: JAX's persistent cache.

A cold process compiles every kernel and program again; on the chip that
is a large share of a short run.  :func:`enable_compile_cache` turns JAX's
persistent compilation cache on, so a second process with the same
programs reads them back instead:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it as its cache
  directory, and this code sets no other;
* otherwise the cache is ``.jax_cache/`` at the root of the checkout — a
  fixed path, because the path is part of what a cache entry is found by.

Entry points call it first: ``chip_smoke.py``, ``repro.launch.serve.main``
and ``benchmarks/run.py``.  The library never turns the cache on by itself.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "enable_compile_cache"]

# src/repro/jax_cache.py -> the checkout root.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # Kernels compile in well under JAX's default 1 s floor; cache them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
