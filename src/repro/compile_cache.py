"""Where the command-line entry points keep JAX's persistent compile cache.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set, that
directory is the cache and nothing here overrides it. When it is not set,
`configure()` points the cache at `<checkout>/.jax_cache`. The path is
fixed — never built from a temporary name, a process id or the time — so
a later run from the same checkout finds what an earlier one compiled
(the path is part of the cache key). `.gitignore` lists the directory.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
REPO_CACHE_DIR = CHECKOUT / ".jax_cache"


def configure() -> str:
    """Enable the persistent compile cache; returns the directory used."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
