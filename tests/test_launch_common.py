"""The persistent compilation cache helper the launchers call: it follows
``JAX_COMPILATION_CACHE_DIR`` when set and otherwise uses the fixed
``.jax_cache/`` directory in the checkout."""
import os

import jax
import pytest

from repro.launch import common


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_follows_env(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert common.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set


def test_cache_defaults_to_checkout_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = common.enable_compile_cache()
    assert path == os.path.join(common.REPO_ROOT, ".jax_cache")
    assert os.path.isfile(os.path.join(common.REPO_ROOT, "pyproject.toml"))
    assert jax.config.jax_compilation_cache_dir == path
    assert common.enable_compile_cache() == path  # fixed, not per call
