"""The persistent compilation cache helper of the entry points."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_stands(monkeypatch, restore_cache_dir):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_at_repo_root(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.setup_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.setup_compile_cache() == got  # same every call
