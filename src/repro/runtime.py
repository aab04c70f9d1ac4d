"""Process-level JAX set-up shared by the entry points.

* :func:`enable_compile_cache` places JAX's persistent compilation cache.
* :func:`backend_initialized` tells whether this process has created a JAX
  backend — on a TPU host, whether it holds the chip.  A chip belongs to
  one process at a time, so a parent that starts a JAX child must not.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache", "backend_initialized"]

#: the cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (listed in ``.gitignore``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`, which never moves: the directory is part of
    what a later run must find again.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def backend_initialized() -> bool:
    """True once this process has created a JAX backend (and so, on a TPU
    host, taken the chip)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()
