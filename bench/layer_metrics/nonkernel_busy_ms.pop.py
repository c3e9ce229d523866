"""Device busy time per round outside the ``ota_shard_tx`` kernel: the
Theorem-4 search, the local updates and the cross-block combine, in ms."""

from bench import kernel_names


def read(ctx):
    from bench.trace_reduce import kernel_seconds
    r = ctx.reduced
    kernel = kernel_seconds(r, kernel_names.OTA_SHARD_TX) or 0.0
    return 1e3 * (r["busy_s"] - kernel) / ctx.window["rounds"]
