"""Persistent XLA compilation cache for the entry points that run on a chip.

One knob: `JAX_COMPILATION_CACHE_DIR`. When it is set, jax reads it itself
and nothing here overrides it. When it is unset, the cache lives at the
fixed path `<repo>/.xla_cache` — the path is part of jax's cache key, so a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def enable_persistent_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    os.makedirs(REPO_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
