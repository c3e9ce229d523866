"""The MLP reference against the system's grid path (Pallas backend, CPU
interpret mode) at a test's size, and the bfloat16 control refused."""

import pytest

from bench.tests import helpers

SMALL = {"rounds": 3, "seeds_per_policy": 2}


@pytest.fixture(scope="module")
def cell():
    ctx, gen = helpers.context("mlp_paper.grid", traffic=SMALL)
    state = gen.setup(ctx)
    yield ctx, gen, state
    gen.release(ctx, state)


def test_grid_matches_the_reference(cell):
    ctx, gen, state = cell
    grids, checks, ok = helpers.run(ctx, gen, state)
    assert ok, checks
    assert set(checks) == set(ctx.config["limits"])
    # same seeds, same data: the trajectories agree to float32 rounding
    assert max(c["value"] for c in checks.values()) < 1e-5


def test_bf16_control_is_refused(cell):
    ctx, gen, state = cell
    grids = gen.window(ctx, state, 0.0)
    checks = gen.check(ctx, gen.control_answers(ctx, state, grids))
    assert not helpers.correct(checks), checks
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    # at three rounds the early gaps are still small; the final
    # parameters are already far off
    assert {"params_median.perfect", "params_median.random"} <= failed
