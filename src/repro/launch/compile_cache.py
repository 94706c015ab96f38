"""JAX's persistent compilation cache for the repo's entry points.

Called from a ``main`` (``chip_smoke.py``, ``benchmarks/serve_bench.py``,
``repro.launch.serve``), never on import and never from tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path
inside the checkout, because the path is part of what a later run must
find again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
