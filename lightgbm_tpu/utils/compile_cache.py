"""Persistent XLA compilation cache, on by default at a fixed path.

A 1M-row tree program takes the chip's compiler about 40 s per block
length, so a cold run is mostly compile.  JAX's persistent compilation
cache replays compiled executables across processes, keyed by program,
jaxlib version, backend and the cache's own path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set (or the user configured
``jax_compilation_cache_dir`` before importing this package), that
directory is used and none is set here.  Otherwise the cache lives at
``<checkout>/.jax_cache`` — fixed, never a temporary, pid- or
time-derived path, because the path is part of the key and a directory
that moves never hits.  Every process of a run (the CLI, the tests'
workers, ``chip_smoke.py``) therefore shares one directory.
"""
from __future__ import annotations

import os

# the directory that holds the ``lightgbm_tpu`` package: the checkout
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_default_compile_cache() -> str:
    """Point JAX's persistent cache at the default directory unless one
    is configured already; returns the directory in use."""
    import jax
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache even fast compiles: the block program's cost is the sum of
    # many medium-sized waves
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return DEFAULT_CACHE_DIR
