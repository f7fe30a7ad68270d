"""``repro.compile_cache``: where the programs keep JAX's compile cache."""
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_respects_env_dir(monkeypatch, tmp_path,
                                        restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX


def test_compile_cache_default_is_one_fixed_dir(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first
    assert first == str(DEFAULT_CACHE_DIR) == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
