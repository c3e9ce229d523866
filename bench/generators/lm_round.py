"""Generator of worker-sharded rounds whose local model is a language
model at its published widths (``moonlight_lm``: Moonlight-16B-A3B's
one-chip share, D = 568,484,352), one round program jitted once and its
rounds dispatched back to back, the state donated.

Traffic parameters (``bench/traffic/<mix>.json``): ``U`` workers in
blocks of ``workers_per_block``, one SGD step a round on ``k_b``
sequences of ``seq_len`` tokens per worker; ``data_seed`` draws the
worker data.  Configuration keys: the
model's ``config.json`` widths, ``k_bar`` (K_i ~ round(U[k_bar - 5,
k_bar + 5]) sequences a worker) and the system settings (``lr``,
``case``, ``policy``, ``channel``, ``sigma2``, ``p_max``, ``backend``).

The worker data are fixed for the cell (``data_seed``), so every seed
runs one compiled round, read back from the compile cache after a
cell's first run; the initial parameters and the round keys (which draw
each round's minibatches and channel) come from ``--seed``.  End-to-end
metric: ``pop_round_s``, the time from the window's start to the end of
its last round over the rounds run.  After the window (outside the
trace) the model's ``metrics`` on the final parameters over the task's
fixed test sequences give ``expert_load_max``; the final parameters
are on the host by then, so the reference's check has the chip's memory
to itself.
"""

from __future__ import annotations

import sys
import time

import numpy as np

_STATS = {"selected": "selected", "b": "b_mean", "a_t": "a_t",
          "b_t": "b_t", "eta": "eta", "snr": "snr"}


def shape_of(config: dict) -> dict:
    """The model's widths under ``repro.fl.lm_models.LMShape``'s names
    (the reference reads the same dict)."""
    c = config
    return {"hidden": c["hidden_size"],
            "n_dense": c["first_k_dense_replace"],
            "n_moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "dense_ff": c["intermediate_size"],
            "heads": c["num_attention_heads"],
            "nope": c["qk_nope_head_dim"], "rope_dim": c["qk_rope_head_dim"],
            "v_dim": c["v_head_dim"], "kv_latent": c["kv_lora_rank"],
            "n_experts": c["router_experts"],
            "n_held": c["n_routed_experts"],
            "expert_ff": c["moe_intermediate_size"],
            "shared_ff": c["n_shared_experts"] * c["moe_intermediate_size"],
            "top_k": c["num_experts_per_tok"],
            "routed_scale": c["routed_scaling_factor"],
            "vocab": c["vocab_size"], "rope_theta": float(c["rope_theta"]),
            "eps": c["rms_norm_eps"], "init_std": c["init_std"],
            "expert_offset": c["expert_offset"]}


def _keys(seed: int):
    """(params, rounds) keys of a seed of any size."""
    import jax
    root = jax.random.PRNGKey(seed % 2 ** 32)
    if seed >= 2 ** 32:
        root = jax.random.fold_in(root, seed // 2 ** 32)
    return jax.random.split(root, 2)


def setup(ctx):
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core.channel import ChannelConfig
    from repro.core.convergence import LearningConstants
    from repro.core.objectives import Case
    from repro.data.tasks import build_task_data
    from repro.fl import engine as engine_lib
    from repro.fl.trainer import pad_workers

    c, t = ctx.config, ctx.traffic
    U = int(t["U"])
    data_seed = int(t["data_seed"])
    task, workers, test = build_task_data(
        c["task"], U=U, k_bar=c["k_bar"], data_seed=data_seed,
        seq_len=t["seq_len"], **shape_of(c))
    X, Y, mask, k_i = pad_workers(workers)
    cfg = engine_lib.FLConfig(
        rounds=1, lr=c["lr"], policy=c["policy"], case=Case(c["case"]),
        k_b=int(t["k_b"]), channel_model=c["channel"],
        channel=ChannelConfig(sigma2=c["sigma2"], p_max=c["p_max"]),
        constants=LearningConstants(sigma2=c["sigma2"]),
        backend=c["backend"],
        worker_sharding=U // int(t["workers_per_block"]))
    k_params, _ = _keys(ctx.seed)
    eng = engine_lib.build_engine(task, X, Y, mask, k_i, cfg,
                                  jax.eval_shape(task.init, k_params))
    init_flat = jax.jit(lambda k: ravel_pytree(task.init(k))[0])

    def _round(w2, flat, rest):
        st, stats = eng.step(engine_lib.RoundState(
            flat=flat, w_prev2=w2, delta=rest[0], t=rest[1], key=rest[2],
            chan=rest[3]))
        return st.flat, st.w_prev2, (st.delta, st.t, st.key, st.chan), stats

    # both (D,) state vectors donated: the new parameters take w_{t-2}'s
    # buffer and w_{t-1} passes through as the next round's w_{t-2}
    step = jax.jit(_round, donate_argnums=(0, 1))
    state = {"step": step, "init_flat": init_flat, "eng": eng,
             "task": task, "test": test, "data_seed": data_seed}
    # warm-up: one round from a throwaway state of the window's shapes
    jax.block_until_ready(step(*_start(ctx, state))[0])
    ctx.window["U"], ctx.window["S"] = U, cfg.worker_sharding
    return state


def _start(ctx, state):
    """The donated round arguments (w_{t-2}, w_{t-1}, rest) of round 0."""
    import jax.numpy as jnp
    k_params, k_round = _keys(ctx.seed)
    flat0 = state["init_flat"](k_params)
    st = state["eng"].init(flat0, k_round)
    return (flat0 + jnp.zeros((), flat0.dtype), st.flat,
            (st.delta, st.t, st.key, st.chan))


def reseed(ctx, state):
    """Give a set-up cell a new seed (``bench/readings.py``): new
    parameters and round keys, the same worker data."""


def window(ctx, state, seconds):
    import jax
    step = state["step"]
    w2, flat, rest = _start(ctx, state)
    stats = []
    t0 = time.time()
    while True:
        with jax.profiler.TraceAnnotation("bench.lm.round"):
            flat, w2, rest, s = step(w2, flat, rest)
            jax.block_until_ready(flat)
        stats.append(s)
        if time.time() - t0 >= seconds:
            break
    t1 = time.time()
    ctx.window.update(elapsed_s=t1 - t0, rounds=len(stats))
    # the final parameters leave the device: the reference's check then
    # has the chip's memory to itself
    del w2
    return {"flat": np.asarray(flat), "rounds": len(stats),
            "data_seed": state["data_seed"],
            "stats": {k: np.asarray([getattr(s, k) for s in
                                     jax.device_get(stats)])
                      for k in _STATS.values()}}


def end_to_end(ctx, state, answers):
    import jax
    import jax.numpy as jnp
    # the program counter: routed load of the held experts on the
    # window's final parameters, over the task's fixed test sequences
    x, y = state["test"]
    m = jax.jit(lambda flat: state["task"].metrics(
        state["eng"].unravel(flat), jnp.asarray(x), jnp.asarray(y)))(
            jnp.asarray(answers["flat"]))
    ctx.window["expert_load_max"] = float(m["expert_load_max"])
    ctx.window["ce_final"] = float(m["ce"])
    return {"pop_round_s": ctx.window["elapsed_s"] / answers["rounds"]}


def attempted_failed(ctx, answers):
    finite = np.all(np.isfinite(answers["flat"]))
    return answers["rounds"], 0 if finite else 1


def release(ctx, state):
    state.clear()


def _reference(ctx, answers, precision):
    """(start (D,), final (D,), {stat: (rounds,)}) of the plain
    reference from the run's seeds."""
    import jax
    import jax.numpy as jnp
    from bench.reference import lm_data, moonlight_fl

    c, t = ctx.config, ctx.traffic
    shape = shape_of(c)
    X, Y, mask, k_i = lm_data.lm_task(int(t["U"]), c["k_bar"],
                                      answers["data_seed"], shape["vocab"],
                                      seq_len=t["seq_len"])
    ref = moonlight_fl.Round(shape, X, Y, mask, k_i, lr=c["lr"],
                             k_b=int(t["k_b"]), sigma2=c["sigma2"],
                             p_max=c["p_max"], precision=precision)
    k_params, k_round = _keys(ctx.seed)
    flat0 = moonlight_fl.init_flat(k_params, shape)
    start = np.asarray(flat0)
    state = [flat0, flat0 + jnp.zeros((), flat0.dtype), jnp.float32(0.0),
             jnp.int32(0), k_round]
    series = []
    for _ in range(answers["rounds"]):
        state, s = ref.step(state)
        series.append(s)
    series = jax.device_get(series)
    final = np.asarray(state[0])
    del state
    return start, final, {_STATS[k]: np.asarray([s[k] for s in series])
                          for k in _STATS}


def control_answers(ctx, state, answers):
    """The window's rounds as the reference computes them with bfloat16
    matmul operands: the control, which ``check`` must refuse."""
    _, flat, stats = _reference(ctx, answers, "bf16")
    return {**answers, "flat": flat, "stats": stats}


def params_gap(prog, ref, start, layout) -> float:
    """Worst leaf of ||prog - ref|| / ||ref - start||: the system's
    distance from the reference over how far the reference moved, so a
    state left unchanged reads 1."""
    worst, ofs = 0.0, 0
    for _, shape in layout:
        n = int(np.prod(shape))
        p, r, s = (np.asarray(a[ofs:ofs + n], np.float64)
                   for a in (prog, ref, start))
        ofs += n
        if not np.all(np.isfinite(p)):
            return float("inf")
        diff = float(np.linalg.norm(p - r))
        if diff:
            moved = float(np.linalg.norm(r - s))
            worst = max(worst, diff / moved if moved else float("inf"))
    return worst


def gaps(ctx, answers) -> dict:
    """The window's rounds again in the plain reference, from the same
    seeds: {"params": the final parameters' worst leaf over the
    reference's own movement, "stats": the worst of every round's
    stats}."""
    from bench import compare
    from bench.reference import moonlight_fl

    start, flat, ref = _reference(ctx, answers, "highest")
    return {
        "params": params_gap(answers["flat"], flat, start,
                             moonlight_fl.layout(shape_of(ctx.config))),
        "stats": compare.worst(
            compare.series_gap(answers["stats"][k], ref[k]) for k in ref),
    }


def check(ctx, answers):
    """The gaps that have a limit, against it (``params``).  The stats
    gap has none and is printed to standard error: the bfloat16 control
    moves the stats no further from the reference than sound runs do
    (PERF.md), so no limit between the two readings exists."""
    limits = ctx.config["limits"]
    out = {}
    for k, v in gaps(ctx, answers).items():
        if k in limits:
            out[k] = {"value": v, "limit": limits[k]}
        else:
            print(f"reading {k}: {v!r} (no limit)", file=sys.stderr)
    return out
