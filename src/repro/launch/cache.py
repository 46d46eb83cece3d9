"""Where JAX keeps its persistent compilation cache for this checkout.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache` before
their first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and
nothing else is configured.  Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored): the directory is
part of every entry's key, so a path that moved between runs would never
hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache should use."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    (a no-op when JAX already read it from the environment) and return the
    directory."""
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
