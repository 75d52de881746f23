"""The benchmark runs in float32 (x64 off) and, under test, leaves JAX's
persistent compilation cache alone: whatever another conftest in the same
process turned on is restored after each test."""
import jax
import pytest


@pytest.fixture(autouse=True)
def float32_no_cache(monkeypatch):
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    from bench import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    yield
    jax.config.update("jax_enable_x64", x64)
