"""Mean ``cohort.dispatch`` span of the traced window (trace, lower,
compile or cache read, and enqueue of one cohort on a daemon dispatch
thread), from the system's own spans, in ms."""

from bench import program_spans


def read(ctx):
    return program_spans.mean_ms("cohort.dispatch")
