"""Where the entry points put JAX's persistent compilation cache."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # stable across calls
