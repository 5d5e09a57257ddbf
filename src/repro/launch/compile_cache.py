"""Persistent compilation cache for the entry points.

Called by the command-line entry points (``chip_smoke.py``,
``repro.launch.bench_serve``, ``repro.launch.serve``, ``benchmarks/run.py``)
before their first compile -- never on import and never from tests.  JAX
keys the cache on the directory, so the default is a fixed path inside the
checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``
    (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
