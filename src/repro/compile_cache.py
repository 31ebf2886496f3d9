"""JAX's persistent compilation cache, for the entry points only.

Scripts that drive the system (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``) call :func:`use_compile_cache` first, so a cold run
on the chip reuses programs an earlier run compiled.  Library import and
the tests never call it.
"""

from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

# fixed per checkout: the path is part of what a cache hit needs
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache goes to ``<repo root>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
