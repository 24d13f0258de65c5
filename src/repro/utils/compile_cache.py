"""Where compiled XLA programs persist between processes.

A whole-width model compiles for a minute or more per program, and every
fresh process starts with no compiled code.  JAX's persistent compilation
cache keeps the executables on disk so the next process finds them.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``.jax_cache/`` at the root of the checkout this module sits in
#: (``<root>/src/repro/utils/compile_cache.py``).
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives at the fixed
    :data:`CHECKOUT_CACHE`, never at a temporary, per-process or dated
    path, so a later run of the same checkout hits what this one wrote.
    Call once from an entry point, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
