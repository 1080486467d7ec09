"""Persistent XLA compilation cache for the repo's entry points.

Called by each entry point (``chip_smoke.py``, ``launch/train_forest.py``,
``examples/serve_seizure.py``, ``benchmarks/run.py``) before its first
compile, never on import, so tests and library users keep whatever cache
setting they chose.
"""

from __future__ import annotations

import os
import pathlib

import jax

# Fixed, git-ignored path inside the checkout: the cache directory is part
# of every entry's key, so a path that moved between runs would never hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
