"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so it must not move
between runs: `JAX_COMPILATION_CACHE_DIR` when the environment sets it,
else one fixed directory inside the checkout. Entry points call
`enable_compile_cache()`; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the fixed
    path inside the checkout."""
    return os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory. An environment-given directory is left to JAX, which
    reads the variable itself; no other directory is set in code then.
    Every program is cached, however short its compile: the codec's
    kernels compile in about a second each."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
