"""Generator of the batch-grid traffic: whole experiment grids through
``repro.sweep.run_spec``, back to back, as a researcher runs them.

Traffic parameters (``bench/traffic/<mix>.json``):

  policies          the grid's policy axis
  seeds_per_policy  the grid's seed axis (fresh seeds for every grid)
  rounds            rounds per experiment
  data_seed         the worker data, fixed for the run so that cohort
                    shapes never change between grids

Every grid's seeds come from ``--seed``: the same seed gives the same
grids in the same order.  End-to-end metric: ``grid_exp_rounds_per_s``,
all experiment-rounds of the grids issued in the window over the time
from the first issue to the last grid's results on the host.
"""

from __future__ import annotations

import time

import numpy as np

SEED_MAX = 2 ** 31 - 1


def spec(ctx, policies, seeds):
    """The grid ``policies`` x ``seeds`` of the cell's configuration."""
    from repro.sweep import SweepSpec
    c, t = ctx.config, ctx.traffic
    base = {k: c[k] for k in ("task", "U", "k_bar", "lr", "case", "k_b",
                              "sigma2", "p_max", "channel", "backend")}
    base.update(rounds=t["rounds"], data_seed=t["data_seed"])
    return SweepSpec(axes={"policy": tuple(policies),
                           "seed": tuple(int(s) for s in seeds)},
                     base=base)


def _run(ctx, state, seeds):
    import jax
    from repro.sweep import run_spec
    with jax.profiler.TraceAnnotation("bench.grid.run_spec"):
        return run_spec(spec(ctx, ctx.traffic["policies"], seeds))


def setup(ctx):
    state = {"rng": np.random.default_rng(ctx.seed)}
    n = ctx.traffic["seeds_per_policy"]
    # warm-up: one grid of the window's shapes on seeds it never uses
    _run(ctx, state, state["rng"].integers(0, SEED_MAX, n))
    return state


def reseed(ctx, state):
    """Give a set-up cell a new seed (``bench/readings.py``)."""
    state["rng"] = np.random.default_rng(ctx.seed)


def window(ctx, state, seconds):
    n = ctx.traffic["seeds_per_policy"]
    grids = []
    t0 = time.time()
    while True:
        seeds = state["rng"].integers(0, SEED_MAX, n)
        grids.append((seeds, _run(ctx, state, seeds)))
        if time.time() - t0 >= seconds:
            break
    t1 = time.time()
    exp_rounds = sum(len(res) for _, res in grids) * ctx.traffic["rounds"]
    ctx.window.update(elapsed_s=t1 - t0, grids=len(grids),
                      exp_rounds=exp_rounds,
                      inflota_exp_rounds=exp_rounds * sum(
                          p == "inflota" for p in ctx.traffic["policies"])
                      // len(ctx.traffic["policies"]))
    return grids


def end_to_end(ctx, state, grids):
    return {"grid_exp_rounds_per_s":
            ctx.window["exp_rounds"] / ctx.window["elapsed_s"]}


def attempted_failed(ctx, grids):
    cells = [r for _, res in grids for r in res]
    return len(cells), sum(r is None for r in cells)


def release(ctx, state):
    state.clear()


def control_answers(ctx, state, grids):
    """The window's grids as the reference computes them in bfloat16: the
    control, which ``check`` must refuse."""
    from bench.reference import mlp_fl
    ref = mlp_fl.Reference(ctx.config, ctx.traffic["data_seed"], "bf16")
    out = []
    for seeds, results in grids:
        runs = {p: ref.run(p, seeds, ctx.traffic["rounds"])
                for p in ctx.traffic["policies"]}
        pos = {int(s): e for e, s in enumerate(seeds)}
        out.append((seeds, [
            {"cell": r["cell"],
             "history": {k: runs[r["cell"]["policy"]][k][
                 pos[r["cell"]["seed"]]] for k in mlp_fl.SERIES},
             "flat": runs[r["cell"]["policy"]]["flat"][
                 pos[r["cell"]["seed"]]]} for r in results]))
    return out


def check(ctx, grids):
    """Every cell of every grid of the window against the plain reference: the per-round history over the first rounds
    (``early``), the whole history and the final parameters, each per
    policy and summed up over the cells as the configuration's limits
    name it (``_max`` the worst cell, ``_median`` the median cell;
    PERF.md says why)."""
    from bench import compare
    from bench.reference import mlp_fl
    c = ctx.config
    ref = mlp_fl.Reference(c, ctx.traffic["data_seed"])
    gaps = {}
    for seeds, results in grids:
        for policy in ctx.traffic["policies"]:
            out = ref.run(policy, seeds, ctx.traffic["rounds"])
            for e, seed in enumerate(seeds):
                res = next(r for r in results
                           if r is not None and r["cell"]["policy"] == policy
                           and r["cell"]["seed"] == int(seed))
                early, whole = compare.history_gaps(
                    res["history"], {k: out[k][e] for k in mlp_fl.SERIES},
                    mlp_fl.SERIES, c["early_rounds"])
                params = compare.leaf_gap(
                    mlp_fl.leaves(np.asarray(res["flat"])),
                    mlp_fl.leaves(out["flat"][e]))
                for name, v in (("early", early), ("history", whole),
                                ("params", params)):
                    gaps.setdefault((name, policy), []).append(v)
    return compare.summarize(gaps, c["limits"])
