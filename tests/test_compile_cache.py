"""Where the persistent compilation cache goes (`repro.compile_cache`)."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import DEFAULT_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_cache_defaults_to_fixed_dir_in_checkout(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(DEFAULT_DIR) == jax.config.jax_compilation_cache_dir
    checkout = Path(__file__).resolve().parents[1]
    assert DEFAULT_DIR == checkout / ".jax_cache"
    assert enable_compile_cache() == path        # stable across calls


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                   restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
