"""Persistent XLA compile cache for the programs that drive the chip.

A cold compile of a published-width serving step takes tens of seconds
per phase; the persistent cache lets a later process on the same
checkout load what an earlier one compiled. Called by the entry points
(``chip_smoke.py``, ``python -m repro.launch.serve``,
``benchmarks/serve_bench.py``), never by library code or the tests.
"""
from __future__ import annotations

import os
import pathlib

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, so every run of this checkout finds the
# entries of the last one (git-ignored)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    import jax

    path = os.environ.get(_ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
