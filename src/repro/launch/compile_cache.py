"""JAX's persistent compilation cache, shared by the launchers and
``chip_smoke.py``: a second run of the same programs loads them instead of
compiling again.

The cache directory is part of what a later run looks up, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable itself, and
nothing here overrides it), else the fixed ``<repo>/.jax_cache`` (listed in
``.gitignore``).

A program's op metadata is part of its key. JAX leaves it out by default,
and then a program that differs from a cached one only in its op names (a
``jax.named_scope`` added, moved or dropped) loads the cached executable,
whose profiler events carry the old names: a trace would attribute device
time to layers as the source no longer names them.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
