"""The persistent compilation cache helper (launch/compile_cache.py)."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py").exists()
    assert compile_cache.use_compile_cache() == got      # stable across calls
