"""The entry points' compile-cache helper (``repro.launch.jax_cache``)."""
import os

import jax
import pytest

from repro.launch import jax_cache


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_variable_wins_and_nothing_is_set(monkeypatch, cache_dir_config,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert jax_cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_directory(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jax_cache.enable_compilation_cache()
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax_cache.enable_compilation_cache() == path    # same every call
