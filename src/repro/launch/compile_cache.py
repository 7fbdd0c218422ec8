"""Where JAX keeps its persistent compilation cache.

One directory per checkout, so a second run of the same program finds the
executables the first one compiled.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and nothing here overrides it.  Otherwise
the cache goes to ``<repo>/.jax_cache`` (listed in ``.gitignore``) — a fixed
path, because the path is part of what JAX keys its entries on.
"""

from __future__ import annotations

import os

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
