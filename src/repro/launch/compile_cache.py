"""Persistent compilation cache shared by every launcher.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when that is set it wins
and nothing here overrides it.  Otherwise the cache goes to one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (gitignored), so a
later process on the same checkout finds what an earlier one compiled.
The path never holds a temp name, a pid or a time: a cache that moves
never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path in
    use.  Call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
