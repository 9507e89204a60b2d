"""Where JAX keeps its persistent compilation cache for this repo's scripts.

Called first by the command-line entry points (``chip_smoke.py`` and the
``benchmarks`` mains), never on library import or in tests. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
overrides it; otherwise the cache goes to ``<checkout>/.jax_cache``. The
path is fixed on purpose: it is part of the cache key, so a directory built
from a temporary name, a PID or the time would never hit.
"""
from __future__ import annotations

import os

#: the checkout root (``src/repro/jax_cache.py`` → three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second: the
    # service compiles one small program per (route, shape, batch size)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
