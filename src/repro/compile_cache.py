"""JAX's persistent compilation cache, placed from outside or at the repo.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``repro.launch.train``,
``repro.launch.serve``) call :func:`enable` first thing in ``main()``; nothing
turns the cache on at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into
    its own ``jax_compilation_cache_dir`` setting and nothing is set here.
    Otherwise the cache lives at the fixed ``<repo>/.jax_cache`` — a fixed
    path, because the directory is part of what a later run must find."""
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
