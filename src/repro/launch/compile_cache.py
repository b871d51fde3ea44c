"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory and JAX
reads it itself; otherwise the cache lives at the fixed path ``.jax_cache``
in the root of the checkout.  The path holds no temporary name, process id
or time, so a later run of the same checkout finds the entries again.
Called from
``main()`` of each entry point, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["setup_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
