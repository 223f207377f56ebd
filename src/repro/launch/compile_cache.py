"""JAX's persistent compilation cache, kept at one fixed directory.

A cold run of a real-width model recompiles every step program; the
persistent cache lets later processes load them instead. A cache
directory that moves between runs is never found again, so the path is
fixed.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Call before the first compile, never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here; otherwise the cache goes to ``.jax_cache/`` at the
    checkout root (listed in ``.gitignore``). Returns the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
