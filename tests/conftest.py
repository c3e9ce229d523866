"""Fixtures shared by the test modules."""

import pytest

from repro.obs import trace
from repro.sweep import grid


@pytest.fixture
def fresh_cohort_fns():
    """An empty cohort program cache, whatever ran before in the
    process, and none left behind for the next test: a test that counts
    ``cohort.trace`` events sees its own cohorts traced."""
    grid.cohort_fn_cache_clear()
    yield
    trace.uninstall()
    grid.cohort_fn_cache_clear()
