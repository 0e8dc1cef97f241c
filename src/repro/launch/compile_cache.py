"""Persistent XLA compilation cache, placed from outside.

Full-width programs take tens of seconds each to compile; the cache lets
a later process on the same machine skip that.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself, and nothing here overrides it).
Otherwise the cache lives at one fixed path inside the checkout,
``<repo>/.jax_cache`` (gitignored) — never a path built from a temp name,
a pid or the time, so the next run finds what this one wrote.
"""
from __future__ import annotations

import os

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
