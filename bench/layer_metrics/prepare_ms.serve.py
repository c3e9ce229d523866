"""Mean ``cohort.prepare`` span of the traced window (one cohort's host
data, per-cell keys and closure, on a daemon dispatch thread), from the
system's own spans, in ms."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_ms("cohort.prepare")
