"""Drive a cell's run on the CPU at a test's size, the chip check skipped."""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def context(workload, *, seed=3000000019, config=None, traffic=None):
    """(ctx, gen) for a cell, with its config and traffic overridden."""
    import jax
    from bench import run as bench_run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = bench_run.load_cell(json.load(f), workload)
    loaded["config"].update(config or {})
    loaded["traffic"].update(traffic or {})
    args = argparse.Namespace(seed=seed, seconds=0.0, trace=0)
    ctx = bench_run.Context(args, loaded, jax.devices()[:1],
                            bench_run.CompileClock())
    shutil.rmtree(ctx.scratch, ignore_errors=True)
    return ctx, bench_run.generator_module(loaded["traffic"])


def run(ctx, gen, state=None):
    """One window of the cell (the shortest: one grid, round or request
    each) and its comparison: (answers, checks, correct)."""
    own = state is None
    if own:
        state = gen.setup(ctx)
    answers = gen.window(ctx, state, 0.0)
    if own:
        gen.release(ctx, state)
    checks = gen.check(ctx, answers)
    return answers, checks, correct(checks)


def correct(checks):
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())
