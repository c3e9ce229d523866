"""Microbenchmarks of the OTA compute hot-spots (CPU wall-time).

Times the pure-jnp reference implementations of the per-round hot spots —
the fused OTA transmit/aggregate and the Theorem-4 INFLOTA search — across
D to document the O(D·U) / O(D·U^2) scaling the Pallas kernels tile, plus
the headline before/after: the seed-style round (separate dispatches,
dense (U, D) channel matrix, eager A_t/B_t bookkeeping and per-round host
syncs — the structure of the seed ``use_kernels=True`` path, with the
Pallas interpreter swapped for the jnp reference math so Python
interpreter overhead is excluded) versus the fused single-jit round engine
(``repro.fl.engine.build_ota_stage``: rank-1 channel, beta-free A_t/B_t,
one dispatch, one device sync).

Run as a script it writes ``BENCH_kernels.json`` (override with
``--json PATH``) and prints the ``name,metric,value`` CSV rows.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, channel, inflota
from repro.core import convergence as conv
from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case, case_numerator
from repro.fl.engine import FLConfig, build_ota_stage


def _time(f, *args, reps: int = 5):
    jax.block_until_ready(f(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        # sync INSIDE the rep loop: otherwise all but the last rep time
        # only the async dispatch, understating per-call cost
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def run(U: int = 20):
    rows = []
    c = LearningConstants()
    k_i = jnp.ones((U,)) * 50.0
    p_max = jnp.full((U,), 10.0)
    for D in (1024, 16384, 131072):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
        h = jnp.asarray(rng.exponential(size=(U, D)), jnp.float32)
        noise = jnp.asarray(rng.normal(size=(D,)) * 1e-2, jnp.float32)
        w_abs = jnp.abs(w[0])

        agg_f = jax.jit(lambda w, h, n: aggregation.ota_aggregate(
            w, h, jnp.ones((U,)), jnp.ones((D,)), k_i, 10.0, n)[0])
        us = _time(agg_f, w, h, noise)
        rows.append({"name": f"ota_aggregate_D{D}", "metric": "us_per_call",
                     "value": round(us, 1)})

        sol_f = jax.jit(lambda h, wa: inflota.solve(
            h, k_i, wa, 1e-3, p_max, c, Case.GD_NONCONVEX))
        us = _time(sol_f, h, w_abs)
        rows.append({"name": f"inflota_search_D{D}_U{U}",
                     "metric": "us_per_call", "value": round(us, 1)})
    # bucketed (beyond-paper) search at LM scale
    D = 1 << 20
    wa = jnp.abs(jnp.asarray(np.random.default_rng(1).normal(size=(D,)),
                             jnp.float32))
    hw = jnp.asarray(np.random.default_rng(2).exponential(size=(U,)),
                     jnp.float32)
    f = jax.jit(lambda hw, wa: inflota.solve_bucketed(
        hw, k_i, wa, 1e-3, p_max, c, 256, Case.GD_NONCONVEX))
    us = _time(f, hw, wa)
    rows.append({"name": f"inflota_bucketed_D{D}_nb256",
                 "metric": "us_per_call", "value": round(us, 1)})
    rows.extend(round_engine_rows(U=U))
    return rows


def round_engine_rows(U: int = 20, D: int = 131072):
    """Seed-style round vs the fused jitted engine (jnp reference math)."""
    rng = np.random.default_rng(3)
    c = LearningConstants()
    ch = ChannelConfig()
    k_i = jnp.asarray(rng.integers(25, 35, U), jnp.float32)
    p_max = jnp.full((U,), ch.p_max)
    W = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
    w_prev = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    w_prev2 = w_prev + jnp.asarray(rng.normal(size=(D,)) * 1e-2, jnp.float32)
    key = jax.random.PRNGKey(0)

    # --- seed-style: the structure of the seed use_kernels=True round.
    # Separate jitted dispatches for search and aggregate, a materialized
    # dense (U, D) channel matrix, scalar-eta host sync, eager (unjitted)
    # denominator / A_t / B_t bookkeeping and float() syncs per round.
    solve_f = jax.jit(lambda h, wa, eta: inflota.solve(
        h, k_i, wa, eta, p_max, c, Case.GD_NONCONVEX))
    agg_f = jax.jit(lambda W, h, beta, b, z: aggregation.ota_aggregate(
        W, h, beta, b, k_i, p_max, z)[0])

    def seed_round(W, w_prev, w_prev2, delta_prev):
        kg, kn = channel.round_keys(key, 0)
        h_workers = channel.sample_gains(kg, (U,), ch)
        h = jnp.broadcast_to(h_workers[:, None], (U, D))  # (U, D) in HBM
        noise = channel.sample_noise(kn, (D,), ch)
        eta = float(jnp.mean(jnp.abs(w_prev - w_prev2)) + 1e-8)  # sync 1
        sol = solve_f(h, jnp.abs(w_prev), eta)
        what = agg_f(W, h, sol.beta, sol.b, noise)
        den = aggregation.denominator(sol.beta, k_i, sol.b)       # eager
        new_flat = jnp.where(den > 1e-12, what, w_prev)
        a_t = conv.A_t(sol.beta, k_i, c)                          # eager
        b_t = conv.B_t(sol.beta, sol.b, k_i, c)                   # eager
        delta = float(b_t + a_t * delta_prev)                     # sync 2
        sel = float(jnp.mean(jnp.sum(sol.beta, axis=0)))          # sync 3
        b_used = float(jnp.mean(sol.b))                           # sync 4
        return new_flat, delta, sel, b_used

    us_seed = _time(lambda: seed_round(W, w_prev, w_prev2, 0.1))

    # --- fused: the engine's OTA stage, one jitted graph, rank-1 channel
    cfg = FLConfig(policy="inflota", case=Case.GD_NONCONVEX, channel=ch,
                   constants=c, backend="jnp")
    stage = jax.jit(build_ota_stage(cfg, k_i, D))
    kchan, kpol = jax.random.split(key)

    def fused_round(W, w_prev, w_prev2, delta_prev):
        # () is the memoryless ExpIID channel carry
        return stage(W, w_prev, w_prev2, delta_prev, (), kchan, kpol,
                     jnp.int32(0))

    us_fused = _time(lambda: fused_round(W, w_prev, w_prev2,
                                         jnp.float32(0.1)))
    return [
        {"name": f"round_seed_style_D{D}_U{U}", "metric": "us_per_round",
         "value": round(us_seed, 1)},
        {"name": f"round_fused_jnp_D{D}_U{U}", "metric": "us_per_round",
         "value": round(us_fused, 1)},
        {"name": f"round_fused_speedup_D{D}_U{U}", "metric": "x",
         "value": round(us_seed / us_fused, 2)},
    ]


if __name__ == "__main__":
    from benchmarks.common import device_info, emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="path for the JSON baseline (empty to skip)")
    args = ap.parse_args()
    rows = run()
    emit(rows)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"device": device_info(), "rows": rows},
                      fh, indent=2)
            fh.write("\n")
