"""The benchmark runs with JAX's default 32-bit types; some of the
system's test modules turn 64-bit mode on for their whole worker process,
so each benchmark test module turns it off and restores it after."""

import jax
import pytest


@pytest.fixture(scope="module", autouse=True)
def _x64_off():
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)
