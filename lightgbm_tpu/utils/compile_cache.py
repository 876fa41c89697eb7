"""Where compiled programs and tuned kernel plans persist between runs.

One rule for every entry point (CLI, ``engine.train``/``engine.serve``,
``benchmarks/run.py``, ``chip_smoke.py``, the test suite): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and the program sets
no cache directory in code; otherwise a FIXED directory inside the checkout.
The path is part of a cache entry's key, so a directory that moves (a temp
directory, a pid, a timestamp) never hits.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def cache_dir() -> str:
    """The persistent cache directory in effect (jax-free: the plan cache
    resolves its home here without importing jax)."""
    return os.environ.get(_ENV) or _DEFAULT_DIR


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache BEFORE any jit, so only the
    first run of a program shape pays its XLA/Mosaic compile.  Returns the
    directory in effect."""
    import jax
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    # the default gates (1 s, entry size) would skip the many small
    # per-iteration programs whose compiles still add up on the CLI path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir()
