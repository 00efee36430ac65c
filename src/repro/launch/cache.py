"""Persistent XLA compile cache for the entry points.

Every entry point (``chip_smoke.py``, ``examples/fl_end_to_end.py``,
``repro.launch.serve``, ``repro.launch.train``, the ``benchmarks/`` mains)
calls :func:`enable_compile_cache` before its first compile, so a second
run of the same program loads its executables instead of compiling them.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, since the path is part of the key
# under which a cache is found again (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other path is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
