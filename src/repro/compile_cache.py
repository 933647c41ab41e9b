"""Where JAX keeps its persistent compilation cache.

The engine compiles one program per capacity bucket, and a cold process
pays every compile again unless the cache is on.  :func:`enable` places it:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself, and
  nothing is set here;
* otherwise — one fixed directory inside the checkout (``.jax_cache/``,
  git-ignored).  The path is part of the cache key, so it never depends on
  a temp name, a pid or the time.

Call it before the first compile: from the entry scripts (``chip_smoke.py``,
``benchmarks/run.py`` and its per-table children), never at import time.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
