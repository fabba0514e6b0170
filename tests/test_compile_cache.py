"""The entry points' compile cache follows JAX_COMPILATION_CACHE_DIR and
falls back to a fixed directory in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_to_jax(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", "/set/by/jax/from/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/jax/from/env")
    assert enable_compile_cache() == "/set/by/jax/from/env"
    assert jax.config.jax_compilation_cache_dir == "/set/by/jax/from/env"


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (CHECKOUT / "src" / "repro" / "compile_cache.py").is_file()
    assert enable_compile_cache() == path          # stable across calls
    assert Path(path).parent == CHECKOUT
