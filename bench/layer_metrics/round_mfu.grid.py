"""The whole round's share of the chip's peak: the least time the chip
could take for the traced window's experiment-rounds (the larger of their
FLOPs over peak FLOP/s and their bytes over peak bandwidth, counted from
shapes by ``bench/counts/round_mlp.py``) over the device's busy time in
the trace, in percent.  The FLOP bound uses the published bf16 peak,
though the task matmuls run at full f32."""


def read(ctx):
    from bench.counts import round_mlp
    c, w = ctx.config, ctx.window
    peak = ctx.peak()
    policies = ctx.traffic["policies"]
    per_policy = w["exp_rounds"] / len(policies)
    flops = nbytes = 0.0
    for p in policies:
        n = round_mlp.counts(c["U"], c["k_b"], c["d_in"], c["hidden"],
                             c["classes"], c["n_test"], c["D"], p)
        flops += n["flops"] * per_policy
        nbytes += n["bytes"] * per_policy
    least = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.reduced["busy_s"] * len(ctx.devices))
