"""Backend compiles and persistent-cache reads inside the window
(JAX's monitoring events): the sweep layer's re-lowering of each cohort."""


def read(ctx):
    before, after = ctx.window["compile_before"], ctx.window["compile_after"]
    return float(after["compiles"] - before["compiles"]
                 + after["hits"] - before["hits"])
