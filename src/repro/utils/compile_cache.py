"""Where JAX's persistent compilation cache lives for this repository.

Entry points (``chip_smoke.py``, the ``repro.launch`` CLIs,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start;
importing a library module never changes JAX's configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout (gitignored): the cache key includes the
# path, so a directory that moved between runs would never hit
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    nothing else is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
