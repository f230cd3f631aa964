"""Persistent XLA compilation cache, placeable from outside.

Entry points (``chip_smoke.py``, ``examples/serve.py``,
``examples/train_slayformer.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile. The cache key
includes the directory, so the directory is fixed: either
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself; nothing else is set
in code) or ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache lives in: the environment's, else the
    checkout's fixed ``.jax_cache``."""
    return os.environ.get(ENV_VAR) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
