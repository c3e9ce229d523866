"""The held experts' routed load on the window's final parameters: the
most-loaded held expert's routed tokens over the held experts' mean, the
worst MoE layer's, from the task model's ``metrics`` over the task's
fixed test sequences after the window (1 is an even load); nothing where
the generator did not record it."""


def read(ctx):
    return ctx.window.get("expert_load_max")
