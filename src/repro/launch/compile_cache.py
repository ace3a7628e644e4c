"""Where JAX's persistent compilation cache lives for this repo's entry
points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
directory stands and nothing here overrides it. Otherwise the cache is
kept at a fixed path inside the checkout (``<repo>/.jax_cache``, listed
in ``.gitignore``): the directory is part of each entry's key, so a path
built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache and return its directory.
    Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
