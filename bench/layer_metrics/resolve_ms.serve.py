"""Mean ``cohort.resolve`` span of the traced window (the writer's fetch
of a cohort's results to the host), from the system's own spans, in ms."""


def read(ctx):
    d = [ms for name, ms in ctx.window["obs_spans"] if name == "cohort.resolve"]
    return sum(d) / len(d) if d else None
