"""The whole population round's share of the chip's peak: the least time
for the traced window's rounds (the larger of FLOPs over peak FLOP/s and
bytes over peak bandwidth, from ``bench/counts/round_linreg_pop.py``) over
the device's busy time in the trace, in percent.  Bytes bind: the padded
worker data."""


def read(ctx):
    from bench.counts import round_linreg_pop
    c, w = ctx.config, ctx.window
    n = round_linreg_pop.counts(c["U"], float(c["k_bar"]),
                                c["k_bar"] + c["k_spread"], c["D"])
    peak = ctx.peak()
    least = w["rounds"] * max(n["flops"] / peak["flops_bf16"],
                              n["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.reduced["busy_s"] * len(ctx.devices))
