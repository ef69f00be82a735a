"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the chip
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at the fixed in-checkout path
``<repo>/.jax_cache`` (git-ignored). The directory is part of what a
later run looks the cache up by, so it is never temporary, per-process
or time-stamped.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
