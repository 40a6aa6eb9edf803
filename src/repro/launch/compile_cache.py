"""JAX's persistent compilation cache, kept at one fixed path.

A cold run of a 24-layer model compiles several programs (prefill
buckets, the batched decode step, the fused decode loop); the cache lets
the next process load them instead.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it.  Otherwise the
cache lives at ``<checkout>/.jax_cache`` — a fixed path, never a temp,
pid or time-based one, so a later run finds what an earlier one wrote.

Entry points (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` at start-up; importing a
module never does, and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
