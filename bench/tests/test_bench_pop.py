"""The population reference against the worker-sharded round (Pallas
backend, CPU interpret mode) at a test's size; the bfloat16 control and
each fault the cell can have, planted in the system, read incorrect."""

import jax.numpy as jnp
import pytest

from bench.tests import helpers

SMALL = {"U": 2000, "workers_per_block": 100}


def _cell(monkeypatch=None, plant=None):
    ctx, gen = helpers.context("linreg_paper.pop_1e5", config=SMALL)
    if plant is not None:
        plant(monkeypatch)
    return ctx, gen


def test_population_round_matches_the_reference():
    ctx, gen = _cell()
    state = gen.setup(ctx)
    answers = gen.window(ctx, state, 0.5)
    gen.release(ctx, state)
    assert answers["rounds"] >= 1
    checks = gen.check(ctx, answers)
    assert helpers.correct(checks), checks
    ctl = gen.check(ctx, gen.control_answers(ctx, None, answers))
    assert all(c["value"] > c["limit"] for c in ctl.values()), ctl


def _unchanged_state(monkeypatch):
    from repro.fl import worker_shard
    real = worker_shard.build_sharded_engine

    def build(*a, **k):
        eng = real(*a, **k)

        def step(state, _=None):
            new, stats = eng.step(state, _)
            return state._replace(t=new.t, key=new.key), stats
        return eng._replace(step=step)
    monkeypatch.setattr(worker_shard, "build_sharded_engine", build)


def _half_the_workers(monkeypatch):
    """Half of each block's workers left out of the transmit partials."""
    from repro.kernels import ops
    real = ops.ota_shard_tx

    def tx(w, h, h_est, cw, s, b, k_eff, k_i, p_max, wmask=None, **k):
        keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return real(w, h, h_est, cw, s, b, k_eff * keep, k_i * keep,
                    p_max * keep, wmask, **k)
    monkeypatch.setattr(ops, "ota_shard_tx", tx)


def _altered_answer(monkeypatch):
    from repro.fl import worker_shard
    real = worker_shard.build_sharded_engine

    def build(*a, **k):
        eng = real(*a, **k)

        def step(state, _=None):
            new, stats = eng.step(state, _)
            return new._replace(flat=new.flat * (1 + 1e-3)), stats
        return eng._replace(step=step)
    monkeypatch.setattr(worker_shard, "build_sharded_engine", build)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_the_workers,
                                   _altered_answer])
def test_fault_reads_incorrect(monkeypatch, plant):
    ctx, gen = _cell(monkeypatch, plant)
    _, checks, ok = helpers.run(ctx, gen)
    assert not ok, checks
