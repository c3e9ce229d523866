"""``ota_round``'s device time per inflota experiment-round in the trace,
in us.  The kernel's Theorem-4 search runs on the vector unit, whose peak
no public table gives, so its time is reported and not a share of a
roofline."""

from bench import kernel_names


def read(ctx):
    from bench.trace_reduce import kernel_seconds
    secs = kernel_seconds(ctx.reduced, kernel_names.OTA_ROUND)
    if not secs:
        return None
    rounds = ctx.window["inflota_exp_rounds"]
    return 1e6 * secs * len(ctx.devices) / rounds
