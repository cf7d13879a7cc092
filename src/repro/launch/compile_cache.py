"""Persistent compilation cache for the entry points.

Called by scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``),
never on ``import repro``: a library import must not change where a host
program keeps its compiled code. ``JAX_COMPILATION_CACHE_DIR`` wins — JAX
reads it by itself, so nothing is set in code then. Without it the cache
lives at ``<checkout>/.jax_cache`` (gitignored): a fixed path, because the
cache key includes it and a moving directory never hits.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
