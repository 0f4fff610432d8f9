"""The persistent compilation cache is placed from outside: the
environment's directory when it is set, a fixed in-checkout one otherwise."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_config():
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir)
    yield
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", before[0])
    jax.config.update("jax_compilation_cache_dir", before[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_enable_compile_cache(env_dir, monkeypatch, restore_cache_config):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_dir", None)
    path = compile_cache.enable_compile_cache()
    assert jax.config.jax_enable_compilation_cache
    if env_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    else:
        # JAX reads the variable itself; no directory is set in code
        assert path == env_dir
        assert jax.config.jax_compilation_cache_dir is None
