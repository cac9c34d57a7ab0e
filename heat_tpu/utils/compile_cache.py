"""Where entry scripts keep JAX's persistent compilation cache.

The directory is part of the cache's key, so it has to be the same on
every run: a temporary name, a pid or a time in it never hits. Called by
the entry scripts (``chip_smoke.py``, ``benchmarks/run.py``,
``scripts/``, ``examples/``) before their first compile — never by
``import heat_tpu``, which must not decide where a host application
caches.
"""

from __future__ import annotations

import os

import jax

__all__ = ["place_compile_cache"]

#: programs that compile faster than this are not worth a file
_MIN_COMPILE_SECS = 0.1


def place_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    Where a directory is configured already — ``JAX_COMPILATION_CACHE_DIR``
    in the environment, which JAX reads into its own configuration, or an
    earlier call — the cache was placed and nothing is set here.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored),
    with the minimum compile time lowered so that short programs are
    kept too."""
    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS)
    return path
