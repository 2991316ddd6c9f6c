"""Entry points keep the compile cache where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache``."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax"])
def test_use_checkout_cache(env, monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
    got = compile_cache.use_checkout_cache()
    if env is None:
        checkout = Path(__file__).resolve().parents[1]
        assert got == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before
