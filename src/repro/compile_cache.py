"""Persistent XLA compile cache for the entry points.

Called by the scripts users run (``chip_smoke.py``, ``examples/``,
``benchmarks/``), never on ``import repro``: tests and library users keep
whatever cache policy their process already has.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: its entries are found again only under the
    same path, so it is never a temporary, per-process or per-run one.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
