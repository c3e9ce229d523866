"""JAX traces of a cohort's per-experiment function per grid of the
traced window: ``cohort.trace`` events, which fire from inside that
function's Python body and so once per trace, over the grids issued."""

from bench import program_spans


def read(ctx):
    n = len(program_spans.records("cohort.trace"))
    grids = ctx.window.get("grids")
    if not n or not grids:
        return None
    return n / grids
