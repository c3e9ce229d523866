"""A run whose timed path is broken underneath reads ``correct`` false:
each fault the grid cell can have, planted in the system on the CPU."""

import jax.numpy as jnp
import pytest

from bench.tests import helpers

SMALL = {"rounds": 2, "seeds_per_policy": 1}


def _grid(monkeypatch, plant):
    ctx, gen = helpers.context("mlp_paper.grid", traffic=SMALL)
    plant(monkeypatch)
    return helpers.run(ctx, gen)


def _unchanged_state(monkeypatch):
    """A round step that returns its state unchanged."""
    from repro.fl import trainer
    real = trainer.build_engine

    def build(*a, **k):
        eng = real(*a, **k)

        def step(state, _=None):
            new, stats = eng.step(state, _)
            return state._replace(t=new.t, key=new.key), stats
        return eng._replace(step=step)
    monkeypatch.setattr(trainer, "build_engine", build)


def _half_the_workers(monkeypatch):
    """The error-free average taken over half the workers."""
    from repro.core import aggregation
    real = aggregation.fedavg

    def fedavg(w, k_i):
        half = w.shape[0] // 2
        return real(w[:half], jnp.asarray(k_i)[:half])
    monkeypatch.setattr(aggregation, "fedavg", fedavg)


def _altered_answer(monkeypatch):
    """One number of each cell's answer altered where it is produced."""
    from repro.sweep import grid
    real = grid.finalize_cohort

    def finalize(cohort, out, **k):
        res = real(cohort, out, **k)
        for r in res:
            r["history"]["ce"][-1] *= 1.001
        return res
    monkeypatch.setattr(grid, "finalize_cohort", finalize)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_the_workers,
                                   _altered_answer])
def test_fault_reads_incorrect(monkeypatch, plant):
    _, checks, ok = _grid(monkeypatch, plant)
    assert not ok, checks
