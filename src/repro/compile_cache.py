"""Where JAX's persistent compilation cache lives, for every entry point.

Each program that compiles on the device (``chip_smoke.py``, the
``benchmarks/*.py`` mains, ``launch/*.py``) calls :func:`use_compile_cache`
once, before its first compile.  A library module never calls it: importing
``repro`` sets no JAX option.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache: src/repro/compile_cache.py -> parents[2] is the
# checkout.  A fixed path, because the directory is part of what a cache hit
# needs: a name that changes from run to run never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is ``<checkout>/.jax_cache``.
    Every program is cached however fast it compiled: the scheduler's
    programs compile in about a second each, under JAX's default threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
