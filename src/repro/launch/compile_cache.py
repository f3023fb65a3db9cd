"""Where JAX keeps its persistent compilation cache.

`use_compile_cache` is called once at start-up by the programs that
compile the round engine at full size (`chip_smoke.py`,
`examples/train_dpfl.py`). Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing; otherwise the cache goes to
one fixed directory of the checkout, ``<repo>/.jax_cache`` (git-ignored).
The path is part of each entry's key, so it never depends on a
temporary directory, a pid or the time.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else `REPO_CACHE`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
