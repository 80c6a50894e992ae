"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``'s ``main``, ``chip_smoke.py``) call
``use_compile_cache`` before their first compile; importing this module
sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (git ignores it): the directory is part of
    what a later process must find, so it is never temporary, per-process
    or time-stamped."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
