"""The entry points' compilation cache: a fixed directory at the checkout
root, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
import os

import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_fixed_dir_at_checkout_root(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert use_compile_cache() == CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_environment_dir_is_left_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before
