"""Placement of JAX's persistent compilation cache.

Entry points that compile the batched sweep (``chip_smoke.py``,
``scripts/run_sweep.py``, ``scripts/decide.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once, before their first compile, so a
second process on the same checkout reuses the compiled programs instead
of paying the compile again. It is never called at import time, and tests
never call it.
"""

from __future__ import annotations

import os

#: ``<repo>/.jax_cache``: a fixed path inside the checkout (gitignored).
#: The cache key includes the directory, so it must not move between runs.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
    nothing. Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
