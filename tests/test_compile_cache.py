"""The persistent-compilation-cache helper every entry point calls."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_in_checkout_path_without_env(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == first
