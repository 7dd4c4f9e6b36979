"""Where the persistent XLA compilation cache lives.

Every entry point that compiles for a device (``chip_smoke.py``,
``examples/``, ``launch.py``, ``__graft_entry__.py``) calls :func:`enable_compile_cache` before its
first compile.  The directory is part of the cache key, so it is never a
temp name, a pid or a time: either the operator places it from outside
with ``JAX_COMPILATION_CACHE_DIR`` (jax reads that variable itself and
this module sets nothing), or it is the fixed ``.jax_cache`` directory
at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
