"""Placement of JAX's persistent compilation cache by the entry points."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Restore the process-wide cache directory after each test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                   cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                      cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert compile_cache.enable_compile_cache() == path      # stable
