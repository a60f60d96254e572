"""Fixed on-disk places for JAX's compile cache and the block autotuner.

A TPU plan build compiles one kernel per sweep candidate, so a cold
process spends most of its start-up compiling.  JAX's persistent cache
keys entries by their directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself;
no other directory is set in code), otherwise ``.cache/jax`` inside the
checkout.  The autotuner's JSON lives beside it, so a run's bindings come
from this checkout's own sweeps and not from a file under ``$HOME``.
Both directories are git-ignored.
"""
from __future__ import annotations

import os

import jax

from ..kernels import autotune

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
LOCAL_CACHE = os.path.join(CHECKOUT, ".cache")


def enable() -> str:
    """Turn on the persistent compile cache (every compile is kept, however
    short) and point the autotuner at the checkout's JSON unless
    ``$FANTASTIC4_AUTOTUNE_CACHE`` names another.  Returns the compile
    cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(LOCAL_CACHE, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    os.environ.setdefault(autotune.ENV_CACHE,
                          os.path.join(LOCAL_CACHE, "autotune.json"))
    return path
