"""Where the entry points keep JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro.sim import compile_cache
from repro.sim.compile_cache import enable_compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = os.path.join(_REPO, ".jax_cache")
    assert enable_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path        # the same on every call
