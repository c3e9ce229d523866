"""The served cells against the MLP reference at a test's size (Pallas
backend, CPU interpret mode): computed, shared and hit cells alike; the
bfloat16 control and an answer altered where it is produced read
incorrect."""

from bench.tests import helpers

SMALL = {"rounds": 2, "clients": 2}


def test_served_cells_match_the_reference():
    ctx, gen = helpers.context("mlp_paper.serve", traffic=SMALL)
    state = gen.setup(ctx)
    log = gen.window(ctx, state, 0.0)
    gen.release(ctx, state)
    assert gen.attempted_failed(ctx, log) == (len(log), 0)
    checks = gen.check(ctx, log)
    assert helpers.correct(checks), checks
    ctl = gen.check(ctx, gen.control_answers(ctx, None, log))
    assert not helpers.correct(ctl), ctl


def test_altered_answer_reads_incorrect(monkeypatch):
    from repro.sweep import grid
    real = grid.finalize_cohort

    def finalize(cohort, out, **k):
        res = real(cohort, out, **k)
        for r in res:
            r["history"]["ce"][-1] *= 1.001
        return res
    monkeypatch.setattr(grid, "finalize_cohort", finalize)
    ctx, gen = helpers.context("mlp_paper.serve", traffic=SMALL)
    _, checks, ok = helpers.run(ctx, gen)
    assert not ok, checks


def test_the_worse_path_is_compared():
    """A fault on the hit-or-shared path alone, in a third of the cells,
    fails the median it would pass over all cells together."""
    from bench import run as bench_run
    gen = bench_run.load_module(
        f"{helpers.ROOT}/bench/generators/serve.py", "serve_paths")
    limits = {"early_median.inflota": 1e-3, "early_max.random": 3e-4}
    sound = {("early", "inflota"): [5e-7] * 8,
             ("early", "random"): [2e-6] * 8}
    wrong = {("early", "inflota"): [0.3] * 4,
             ("early", "random"): [2e-6] * 4}
    got = gen.worse_path({"computed": sound, "hit_or_shared": wrong},
                         limits)
    assert got["early_median.inflota"]["value"] == 0.3
    assert got["early_max.random"]["value"] == 2e-6
    both = gen.worse_path({"computed": sound, "hit_or_shared": sound},
                          limits)
    assert helpers.correct(both), both
