"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or else to a fixed directory in the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_var_places_the_cache(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # no path set


def test_default_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.setup_compile_cache()
    root = os.path.join(os.path.dirname(__file__), "..")
    assert path == os.path.join(os.path.realpath(root), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.setup_compile_cache() == path      # never moves
