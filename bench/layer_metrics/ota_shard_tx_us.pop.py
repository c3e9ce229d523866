"""``ota_shard_tx``'s device time per population round in the trace (S
block transmits), in us.  The kernel's work is elementwise on the vector
unit, whose peak no public table gives, so its time is reported and not
a share of a roofline."""

from bench import kernel_names


def read(ctx):
    from bench.trace_reduce import kernel_seconds
    secs = kernel_seconds(ctx.reduced, kernel_names.OTA_SHARD_TX)
    if not secs:
        return None
    return 1e6 * secs * len(ctx.devices) / ctx.window["rounds"]
