"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles (``Experiment.run``, the
benchmark scripts, ``chip_smoke.py``, and the scheduler's pool children):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
- otherwise the cache is :data:`CHECKOUT_CACHE_DIR`, one fixed directory
  inside the checkout (listed in ``.gitignore``).

The path is part of what makes a cache hit, so it is never derived from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/core/exec/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[4] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX at the persistent compilation cache; returns its directory.

    Idempotent.  Takes effect for every compile after the call, so entry
    points call it before their first jitted pass.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["CHECKOUT_CACHE_DIR", "ENV_VAR", "use_compile_cache"]
