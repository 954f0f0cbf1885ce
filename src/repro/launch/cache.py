"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, :mod:`repro.launch.serve`,
:mod:`repro.launch.train`, ``benchmarks/run.py``) call
:func:`enable_compile_cache` at start-up — never at import — so that
processes share compiled programs. The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and no other directory is configured), otherwise the fixed
``<checkout>/.jax_cache``: the path is part of the cache key, so it must
not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's root (src/repro/launch/cache.py → three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compiled program and return
    its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # the broker's kernels compile in about a second: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
