"""JAX's persistent compilation cache at a fixed place.

Every entry point that compiles the engine (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/cluster_sim.py``) calls
:func:`enable_compile_cache` before its first compilation.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives in ``.jax_cache`` at the root of the
checkout (listed in ``.gitignore``): a fixed path, because the path is part
of what a later run must find again.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Switch the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
