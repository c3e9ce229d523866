"""Mean ``cohort.prepare`` span of the traced window (one cohort's host
data and closure, on the serial ``run_spec`` path), from the system's own
spans, in ms."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_ms("cohort.prepare")
