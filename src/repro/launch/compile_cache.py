"""JAX persistent compilation cache placement for the entry points.

Called from ``main()`` of ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks.run`` — never at import, so a library user keeps whatever
cache policy they set. ``JAX_COMPILATION_CACHE_DIR`` wins when it is set
(JAX reads it itself; no other directory is configured then). Otherwise the
cache lives at one fixed, git-ignored directory of the checkout: the path
is part of the cache key, so it never depends on a temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
