"""JAX's persistent compilation cache for this checkout's entry points.

Every command-line entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.scenarios``) calls :func:`enable_compile_cache` from
its ``main`` — never at import, so tests and spawned attempt processes
that import these modules leave the process's cache settings alone.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The fixed fallback location (git-ignored).  The cache key includes
#: the directory, so it must not move between runs: no temp, pid or
#: time-based path.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to the checkout's
    ``.jax_cache``.  Without jax (an optional dependency) there is
    nothing to cache: returns None and does nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    try:
        import jax
    except ImportError:
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
