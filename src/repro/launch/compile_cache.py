"""JAX's persistent compilation cache at one fixed path inside the checkout.

The cache key includes the directory, so a path that moves between runs
(tmp, pid or time based) never hits. Entry points call ``use_compile_cache``
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs in ``CACHE_DIR``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then reads that variable
    itself and nothing is overridden. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
