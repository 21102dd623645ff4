"""Where JAX keeps its persistent compile cache for the launchers."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache key includes the path, so a
# directory named after a process, a temporary or the time never hits.
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; call at the start of a
    launcher's main, never at import. ``JAX_COMPILATION_CACHE_DIR``, when
    set, is read by JAX itself and wins; otherwise the cache goes to
    ``.jax_cache/`` at the repository root (listed in .gitignore)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
