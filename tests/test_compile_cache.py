"""The entry points' compile cache: the environment's directory wins, and
otherwise the checkout's fixed one is used."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_is_left_to_jax(monkeypatch, tmp_path,
                                               restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_directory_without_environment(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
