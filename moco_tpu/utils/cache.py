"""Persistent XLA compilation cache, at ONE place.

XLA compiles at trace time — minutes for the full R50 aug+step program —
so every entry point that compiles calls `enable_persistent_cache()` before
building a jitted program, and a restarted, resumed or repeated run loads
what the first one compiled.

Where the cache lives is decided outside the program when
`JAX_COMPILATION_CACHE_DIR` is set (JAX reads it itself; nothing here
overrides it), and is `<checkout>/.jax_cache` otherwise. Nowhere else: the
directory is part of the cache key, so a cache that moves never hits.
`MOCO_TPU_NO_CACHE=1` opts a process out (throwaway test children).
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_persistent_cache() -> str | None:
    """Returns the cache dir in effect, or None when opted out."""
    import jax

    if os.environ.get("MOCO_TPU_NO_CACHE"):
        # off for real: with the env var set JAX would cache there anyway
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
