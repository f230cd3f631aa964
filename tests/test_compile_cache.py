"""The compile-cache helper: the environment's directory wins, otherwise a
fixed directory inside the checkout."""
from __future__ import annotations

import os

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_dir_without_env(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    # Fixed: the same path on every call, never per process or per run.
    assert compile_cache.compile_cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
