"""JAX's persistent compilation cache, at one fixed place.

A cold process compiles every program it runs; on a TPU that is seconds
per solver program.  :func:`enable_compile_cache` turns JAX's persistent
cache on so later processes reuse those compiles.  The cache directory is
part of every entry's key, so it must not move between runs:

* ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself; this
  module sets no other path), else
* ``.jax_cache/`` at the root of the checkout this package lives in
  (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

#: the checkout root: ``src/repro/runtime/`` → three levels up
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
CHECKOUT_CACHE_DIR = _CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile (every entry point does)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # solver programs compile in well under JAX's 1 s default threshold
    # on CPU but not on the chip; cache every one of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
