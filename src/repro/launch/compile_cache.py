"""JAX's persistent compilation cache, in one place.

Every entry point that compiles at deployment size (``chip_smoke.py``,
``repro.launch.serve_sketch``, the benchmarks) calls
``enable_compile_cache()`` first, so a second run on the same machine
loads its compiled programs instead of compiling them again.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads that variable itself, so nothing else is set), else the fixed
directory ``.jax_cache`` at the repository root (gitignored). The path
is part of each entry's key, so it never comes from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
