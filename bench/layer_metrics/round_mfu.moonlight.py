"""The whole Moonlight round's share of the chip's peak: the least time
for the traced window's rounds (the larger of FLOPs over peak FLOP/s and
bytes over peak bandwidth, from ``bench/counts/round_moonlight.py``) over
the device's busy time in the trace, in percent.  FLOPs bind; the bound
uses the published bf16 peak, though the task matmuls run at full f32."""


def read(ctx):
    from bench.counts import round_moonlight
    from bench.generators.lm_round import shape_of
    c, t, w = ctx.config, ctx.traffic, ctx.window
    n = round_moonlight.counts(shape_of(c), int(t["U"]), int(t["k_b"]),
                               int(t["seq_len"]), c["D"],
                               int(t["workers_per_block"]))
    peak = ctx.peak()
    least = w["rounds"] * max(n["flops"] / peak["flops_bf16"],
                              n["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.reduced["busy_s"] * len(ctx.devices))
