"""Where the entry points keep JAX's persistent compilation cache.

The cache's directory is part of what a later run looks up, so it must
not move between runs: either the environment names it
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) or it is the
fixed ``.jax_cache/`` at the root of the checkout. Entry points call
:func:`use_compile_cache` once at start-up; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

#: ``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/launch/...``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    directory is set here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
