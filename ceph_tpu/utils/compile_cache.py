"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX reads
the variable itself and nothing here sets another.  Unset, the cache
goes to the fixed in-checkout ``.jax_cache`` (a fixed path, because the
path is part of what a later run must find again).  ``bench.py``,
``chip_smoke.py`` and the tests all call :func:`enable_compile_cache`.
"""

from __future__ import annotations

import os
import pathlib

REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache(min_compile_secs: float = 0.0) -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return cache
