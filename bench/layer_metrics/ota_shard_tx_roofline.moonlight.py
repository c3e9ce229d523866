"""``ota_shard_tx``'s share of its HBM roofline in the Moonlight rounds:
the bytes one round's block transmits must move (``transmit`` of
``bench/counts/round_moonlight.py``, every block) over peak bandwidth
times the kernel's device time per round, in percent.  At one worker a
block the transmit does 12 FLOPs per 20 bytes, so bandwidth binds."""

from bench import kernel_names


def read(ctx):
    from bench.counts import round_moonlight
    from bench.trace_reduce import kernel_seconds
    secs = kernel_seconds(ctx.reduced, kernel_names.OTA_SHARD_TX)
    if not secs:
        return None
    t, w = ctx.traffic, ctx.window
    u_b = int(t["workers_per_block"])
    n = round_moonlight.transmit(u_b, ctx.config["D"])
    least = (int(t["U"]) // u_b) * n["bytes"] / ctx.peak()["hbm_bytes_per_s"]
    per_round = secs * len(ctx.devices) / w["rounds"]
    return 100.0 * least / per_round
