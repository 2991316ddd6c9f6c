"""Fixtures for driving a benchmark run on the CPU."""
import jax
import pytest


@pytest.fixture
def cpu_run(monkeypatch):
    """Keep a test's run off the checkout's compile cache, and leave JAX's
    cache settings as they were."""
    monkeypatch.setattr("repro.compile_cache.use_checkout_cache", lambda: "off")
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
