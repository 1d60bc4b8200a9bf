"""Where JAX's persistent compilation cache lives for this repository.

Called by the entry points (``repro.launch.serve.main``, ``chip_smoke.py``)
before they compile anything; importing ``repro`` never calls it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this sets
  nothing.
* Not set: the cache goes to ``.jax_cache`` at the root of the checkout.
  The path is fixed (no temp name, pid or time) because it is part of the
  cache key: a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
