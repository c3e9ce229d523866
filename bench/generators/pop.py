"""Generator of population-scale traffic: one worker-sharded round program,
jitted once, its rounds dispatched back to back (arXiv 2508.17697's
scaling deployment on the paper's Sec. VI-A task).

Configuration keys: ``U`` workers in blocks of ``workers_per_block``,
each with K_i ~ round(U[k_bar - k_spread, k_bar + k_spread]) samples of
y = -2x + 1 + 0.4n, and the system settings (``lr``, ``case``,
``policy``, ``channel``, ``sigma2``, ``p_max``, ``backend``).  Rounds
run one at a time: each is dispatched once the last has ended.

The worker data, the initial parameters and the round key come from
``--seed`` on the device, in one jitted call, at the same shapes for every
seed.  End-to-end metric: ``pop_round_s``, the time from the window's
start to the end of its last round over the rounds run.
"""

from __future__ import annotations

import time

import numpy as np


def make_data(key, U: int, k_bar: int, spread: int):
    """(X, Y, mask, k_i) of U linreg workers padded to k_bar + spread."""
    import jax
    import jax.numpy as jnp
    kc, kx, kn = jax.random.split(key, 3)
    k_max = k_bar + spread
    counts = jnp.round(jax.random.uniform(kc, (U,), minval=k_bar - spread,
                                          maxval=k_bar + spread))
    x = jax.random.uniform(kx, (U, k_max, 1))
    y = -2.0 * x + 1.0 + 0.4 * jax.random.normal(kn, (U, k_max, 1))
    mask = (jnp.arange(k_max)[None, :] < counts[:, None]).astype(jnp.float32)
    return x * mask[..., None], y * mask[..., None], mask, counts


def _keys(seed: int):
    """(data, params, rounds) keys of a seed of any size."""
    import jax
    root = jax.random.PRNGKey(seed % 2 ** 32)
    if seed >= 2 ** 32:
        root = jax.random.fold_in(root, seed // 2 ** 32)
    return jax.random.split(root, 3)


def setup(ctx):
    import functools

    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core.channel import ChannelConfig
    from repro.core.convergence import LearningConstants
    from repro.core.objectives import Case
    from repro.fl import worker_shard
    from repro.fl.engine import FLConfig
    from repro.fl.models import linreg_model

    c = ctx.config
    U = int(c["U"])
    shards = U // int(c["workers_per_block"])
    k_data, k_params, k_round = _keys(ctx.seed)
    data = jax.jit(functools.partial(make_data, U=U, k_bar=c["k_bar"],
                                     spread=c["k_spread"]))(k_data)
    task = linreg_model()
    params0 = task.init(k_params)
    cfg = FLConfig(rounds=1, lr=c["lr"], policy=c["policy"],
                   case=Case(c["case"]), channel_model=c["channel"],
                   channel=ChannelConfig(sigma2=c["sigma2"],
                                         p_max=c["p_max"]),
                   constants=LearningConstants(sigma2=c["sigma2"]),
                   backend=c["backend"], worker_sharding=shards)
    eng = worker_shard.build_sharded_engine(task, *data, cfg, params0)
    flat0, _ = ravel_pytree(params0)
    st0 = eng.init(flat0, k_round)
    step = jax.jit(eng.step)
    # warm-up: the round program is pure, so one round from the initial
    # state compiles it and leaves the window's start untouched
    jax.block_until_ready(step(st0))
    ctx.window["U"], ctx.window["shards"] = U, shards
    return {"step": step, "st0": st0, "data": data}


def window(ctx, state, seconds):
    import jax
    step, st = state["step"], state["st0"]
    stats = []
    t0 = time.time()
    while True:
        with jax.profiler.TraceAnnotation("bench.pop.round"):
            st, s = step(st)
            jax.block_until_ready(st)
        stats.append(s)
        if time.time() - t0 >= seconds:
            break
    t1 = time.time()
    ctx.window.update(elapsed_s=t1 - t0, rounds=len(stats))
    return {"flat": np.asarray(st.flat), "rounds": len(stats),
            "stats": {k: np.asarray([getattr(s, k) for s in
                                     jax.device_get(stats)])
                      for k in ("selected", "b_mean", "a_t", "b_t", "eta",
                                "snr")},
            "data": state["data"]}


def end_to_end(ctx, state, answers):
    return {"pop_round_s": ctx.window["elapsed_s"] / answers["rounds"]}


def attempted_failed(ctx, answers):
    finite = np.all(np.isfinite(answers["flat"]))
    return answers["rounds"], 0 if finite else 1


def release(ctx, state):
    state.clear()


_NAMES = {"selected": "selected", "b": "b_mean", "a_t": "a_t",
          "b_t": "b_t", "eta": "eta", "snr": "snr"}


def _reference(ctx, data, rounds, precision):
    """(final params, {stat: (rounds,)}) of the plain reference."""
    import jax
    import jax.numpy as jnp
    from bench.reference import linreg_pop

    c = ctx.config
    _, k_params, k_round = _keys(ctx.seed)
    flat0 = linreg_pop.init_params(k_params)
    round_ = jax.jit(linreg_pop.make_round(
        *data, lr=c["lr"], sigma2=c["sigma2"], p_max=c["p_max"],
        precision=precision))
    carry = (flat0, flat0, jnp.float32(0.0), jnp.int32(0), k_round)
    series = []
    for _ in range(rounds):
        carry, s = round_(*carry)
        series.append(s)
    series = jax.device_get(series)
    return np.asarray(carry[0]), {
        _NAMES[k]: np.asarray([s[k] for s in series]) for k in _NAMES}


def control_answers(ctx, state, answers):
    """The window's rounds as the reference computes them in bfloat16:
    the control, which ``check`` must refuse."""
    flat, stats = _reference(ctx, answers["data"], answers["rounds"],
                             "bf16")
    return {**answers, "flat": flat, "stats": stats}


def check(ctx, answers):
    """The window's rounds again in the plain reference, from the same
    seed: every round's stats and the final parameters."""
    from bench import compare
    from bench.reference import linreg_pop

    c = ctx.config
    flat, ref = _reference(ctx, answers["data"], answers["rounds"],
                           "highest")
    gaps = {
        "params": compare.leaf_gap(
            dict(zip(linreg_pop.NAMES, answers["flat"])),
            dict(zip(linreg_pop.NAMES, flat))),
        "stats": compare.worst(
            compare.series_gap(answers["stats"][k], ref[k]) for k in ref),
    }
    limits = c["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
