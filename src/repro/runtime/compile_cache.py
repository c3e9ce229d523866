"""JAX persistent compilation cache for the repo's entry points.

``python -m repro.sweep``, ``python -m repro.serve`` and
``chip_smoke.py`` call :func:`enable` before their first compile, so a
restarted daemon or a repeated run reloads its executables instead of
compiling them again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets nothing.  Otherwise the cache lives
at one fixed directory inside the checkout: the directory is part of
the cache's key, so it never depends on a temporary name, a PID or the
time.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
