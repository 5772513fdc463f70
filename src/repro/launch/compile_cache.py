"""Where JAX keeps its persistent compilation cache.

The cache is keyed by path as well as program, so it only pays off at a
fixed location.  ``JAX_COMPILATION_CACHE_DIR``, when set, decides (JAX reads
the variable itself); otherwise the launchers use ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
