"""`repro.utils.init_compile_cache`: the entry points' one switch for
JAX's persistent compilation cache. An outside `JAX_COMPILATION_CACHE_DIR`
wins and nothing is set in code; otherwise the cache lives at a fixed,
gitignored path inside the checkout, so one run finds what another
compiled."""
import os

import jax
import pytest

from repro.utils import REPO_ROOT, init_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_as_is(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert init_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_gitignored(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = init_compile_cache()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert init_compile_cache() == path          # same path every call
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
