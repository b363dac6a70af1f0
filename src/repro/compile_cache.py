"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``,
``repro.launch``) call :func:`enable_compile_cache` once before their first
compile; library code and the tests never do. The directory is part of what
a later process must find again, so it is never a temporary, per-process or
time-stamped path.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads the
    variable itself) and no other is set; otherwise ``<repo>/.jax_cache``.
    Every compile is cached, however short: a cold run compiles dozens of
    small programs whose times add up.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
