"""JAX persistent compilation cache for the entry points.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before they
compile anything; library code never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets no
other directory.  Otherwise the cache lives at the fixed path
``<checkout>/.cache/jax`` (ignored by git): the directory is part of the
cache key, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
