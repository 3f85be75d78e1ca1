"""The entry points' persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
wins when set; otherwise the cache sits at the fixed `<checkout>/.jax_cache`
(never a temp name, pid or time, which would never hit twice)."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_is_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                       "chip_smoke.py"))
    assert compile_cache.enable_compile_cache() == path


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
