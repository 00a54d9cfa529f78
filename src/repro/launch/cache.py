"""Where JAX keeps its persistent compilation cache.

A process that compiles a full-width model spends most of its start-up in
XLA; the persistent cache lets a later process on the same machine skip
that.  The cache directory is part of what a later process must find again,
so it is never derived from a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

# fixed, git-ignored path inside the checkout
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache lives at ``.jax_cache/`` in the
    checkout.  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]
