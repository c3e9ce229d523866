"""Mean ``store.put`` span of the traced window (one cell document
written to the store), from the system's own spans, in ms."""


def read(ctx):
    d = [ms for name, ms in ctx.window["obs_spans"] if name == "store.put"]
    return sum(d) / len(d) if d else None
