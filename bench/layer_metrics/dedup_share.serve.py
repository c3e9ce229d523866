"""Share of the cells requested in the window that needed no new device
work (store hits, or shared with a cohort in flight), from the daemon's
session counters, in percent."""


def read(ctx):
    cells = ctx.window["cells"]
    asked = cells.get("requested", 0)
    if not asked:
        return None
    return 100.0 * (cells.get("hit", 0) + cells.get("shared", 0)) / asked
