"""repro.utils.compile_cache: where entry points put JAX's persistent cache."""
import os

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_repo_cache_dir_is_fixed_under_the_checkout():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")


def test_placed_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    assert compile_cache.enable_compile_cache() == "/placed/elsewhere"
    # JAX read the variable itself at start-up; the helper sets nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_falls_back_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == compile_cache.REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.REPO_CACHE_DIR
