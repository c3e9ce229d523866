"""Blessing-of-scaling figure: worker-sharded OTA rounds at U = 10^4..10^6.

Reproduces the scaling trend of arXiv 2508.17697 on the paper's Sec. VI
linear-regression task: with channel-inversion power control the OTA
descale denominator grows ~U, so the post-aggregation noise power falls
~U^-2 and the realized SNR climbs with the worker population — the
regime the dense (U, D) engine cannot reach on one host and
``FLConfig.worker_sharding`` exists for.

Each U runs a few worker-sharded INFLOTA rounds (block size ~``u_b``
workers, S = U / u_b shard blocks, never materializing (U, D)) and
reports, per ``common.phase_times`` (block-until-ready per phase, so
numbers are not blended by async dispatch):

  * ``snr_final_db``  realized post-aggregation SNR of the last round;
  * ``round_wall_s``  steady-state end-to-end round time;
  * ``search_s``      the distributed Theorem-4 sorted-prefix search;
  * ``tx_kernel_s``   the S streamed ``ota_shard_tx`` tile kernels;
  * ``combine_s``     the descale with its blockwise beta reduction — the
                      part that becomes a psum/all_gather on a mesh,
                      reported separately from kernel time on purpose.

Worker data is built directly as (U, K) arrays (same generator family
as ``data/synthetic.linreg``) — the partition/pad path would build a
python list of 10^6 worker tuples.

``python -m benchmarks.fig_scaling_u`` merges the ``scaling_u_*`` rows
into BENCH_sweeps.json in place (the sweep-bench doc is otherwise
written wholesale by ``benchmarks.sweep_bench``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from benchmarks import common
from repro.core import inflota
from repro.core.convergence import LearningConstants
from repro.fl import worker_shard
from repro.fl.engine import FLConfig, build_engine
from repro.fl.models import linreg_model
from repro.kernels import ops as kops

_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_sweeps.json")


def _worker_arrays(U: int, K: int = 2, seed: int = 0):
    """(X, Y, mask, k_i) for U equal-sized linreg workers, built flat."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, 1.0, size=(U, K)).astype(np.float32)
    y = (-2.0 * x + 1.0
         + 0.4 * rng.normal(size=(U, K))).astype(np.float32)
    mask = np.ones((U, K), np.float32)
    k_i = np.full((U,), float(K), np.float32)
    return (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
            jnp.asarray(k_i))


def _bench_u(U: int, rounds: int, u_b: int, reps: int) -> Dict[str, float]:
    S = max(U // u_b, 1)
    task = linreg_model()
    X, Y, mask, k_i = _worker_arrays(U)
    params0 = task.init(jax.random.PRNGKey(7))
    cfg = FLConfig(rounds=rounds, lr=0.05, policy="inflota",
                   worker_sharding=S, channel=common.PAPER_CHANNEL,
                   constants=LearningConstants(
                       sigma2=common.PAPER_CHANNEL.sigma2))
    eng = build_engine(task, X, Y, mask, k_i, cfg, params0)
    flat0, _ = ravel_pytree(params0)
    D = flat0.shape[0]

    st = eng.init(flat0, jax.random.PRNGKey(0))
    step = jax.jit(eng.step)
    st, stats = step(st)                       # trace + compile + round 0
    for _ in range(rounds - 1):
        st, stats = step(st)
    jax.block_until_ready(st.flat)
    snr = float(stats.snr)

    # phase thunks over the same shapes the round streams: the search on
    # this round's CSI, one scan of S transmit tile kernels onto the
    # carried y, and the descale with its blockwise beta reduction
    c = cfg.constants
    key = jax.random.PRNGKey(1)
    h = jax.random.exponential(key, (U,))
    w_abs = jnp.abs(st.flat)
    eta = jnp.full((D,), 1e-2, jnp.float32)
    p_max = jnp.full((U,), common.PAPER_CHANNEL.p_max, jnp.float32)

    search = jax.jit(lambda hh: inflota.solve_rank1_sharded(
        hh, k_i, w_abs, eta, common.PAPER_CHANNEL.p_max, c, n_shards=S))
    sol = jax.block_until_ready(search(h))

    blocked = {"h": h.reshape(S, u_b), "cw": sol.cw,
               "k": k_i.reshape(S, u_b), "p": p_max.reshape(S, u_b)}
    Wb = jnp.broadcast_to(st.flat, (u_b, D))

    @jax.jit
    def tx_stream(blk, b, s):
        def body(y, xs):
            return kops.ota_shard_tx(
                Wb, xs["h"], xs["h"], xs["cw"], s, b, xs["k"], xs["k"],
                xs["p"], y=y), None
        y, _ = jax.lax.scan(body, jnp.zeros_like(b), blk)
        return y

    y = jax.block_until_ready(tx_stream(blocked, sol.b, sol.s))

    @jax.jit
    def combine(y, b):
        den = jax.lax.fori_loop(
            0, S, lambda j, acc: acc + jnp.sum(
                blocked["k"][j][:, None] * inflota.block_beta(
                    b, sol.cw[j], sol.s), axis=0), jnp.zeros_like(b))
        return y / jnp.maximum(den * b, 1e-12)

    times = common.phase_times({
        "round_wall_s": lambda: step(st)[0].flat,
        "search_s": lambda: search(h).b,
        "tx_kernel_s": lambda: tx_stream(blocked, sol.b, sol.s),
        "combine_s": lambda: combine(y, sol.b),
    }, reps=reps)
    return {"snr_final_db": 10.0 * float(np.log10(max(snr, 1e-30))),
            "shards": float(S), **times}


def run(rounds: int = 3, us: Sequence[int] = (10_000, 100_000, 1_000_000),
        u_b: int = 1000, reps: int = 3) -> List[dict]:
    rows: List[dict] = []
    for U in us:
        vals = _bench_u(int(U), rounds, u_b, reps)
        rows += [{"name": f"scaling_u_{int(U)}", "metric": k,
                  "value": round(v, 6)} for k, v in vals.items()]
    return rows


def merge_rows(rows: List[dict], json_path: str = _JSON) -> None:
    """Splice ``scaling_u_*`` rows into the sweep-bench JSON doc in
    place, preserving every other section's rows."""
    with open(json_path) as f:
        doc = json.load(f)
    doc["rows"] = [r for r in doc["rows"]
                   if not str(r.get("name", "")).startswith("scaling_u_")]
    doc["rows"] += rows
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    out = run()
    common.emit(out)
    merge_rows(out)
