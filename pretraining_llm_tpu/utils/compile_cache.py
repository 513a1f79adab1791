"""Persistent XLA compilation cache, placed from outside or at a fixed path.

Every entry point calls `use_compile_cache()` before its first jit. A cold
TPU compile of the train step or the serving programs takes tens of seconds;
the cache lets the processes of one job (trainer, server, workers) and a
re-run in the same checkout skip it.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives in `<checkout>/.jax_cache`,
derived from this file's location: the directory is part of what a cache
hit depends on, so it must not move with the cwd, a pid or the clock.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    Touches only JAX's config — no backend is initialised."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
