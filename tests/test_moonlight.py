"""Moonlight-16B-A3B's one-chip share as the FL local model, at a small
size (hidden 64, 2 heads, latent 32, 8 rotary dims, 16 experts of which
8 are held, top 2, vocabulary 256, T = 32, U = 4): the task model
against the plain reference (``bench/reference/moonlight_fl.py``), the
worker-sharded round against the reference round, the expert share
against the uncut layer, and ``run_spec`` into a store."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data
from repro.fl import lm_models
from repro.fl.engine import FLConfig, build_engine
from repro.fl.trainer import pad_workers

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench.reference import lm_data, moonlight_fl  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

SMALL = dict(hidden=64, heads=2, nope=16, rope_dim=8, v_dim=16,
             kv_latent=32, n_experts=16, n_held=8, expert_ff=24,
             shared_ff=48, dense_ff=96, top_k=2, vocab=256, n_moe=2)
T, U, K_BAR = 32, 4, 6


@pytest.fixture(scope="module", autouse=True)
def _float32_mode():
    """Another module may have turned 64-bit mode on for the process;
    module scope, so the module-scoped reference run is float32 too."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


def _shape(**over):
    return lm_models.LMShape(**{**SMALL, **over})


def _ref_cfg(shape):
    return dataclasses.asdict(shape)


def test_published_share_has_the_stated_size():
    assert lm_models.param_count(lm_models.LMShape()) == 568_484_352
    assert moonlight_fl.param_count(_ref_cfg(lm_models.LMShape())) \
        == 568_484_352


def test_logits_equal_the_reference_forward():
    shape = _shape()
    model = lm_models.moonlight_model(shape)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    flat, _ = ravel_pytree(params)
    cfg = _ref_cfg(shape)
    np.testing.assert_array_equal(np.asarray(flat),
                                  np.asarray(moonlight_fl.init_flat(key, cfg)))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, T), 0, 256)
    logits, loads = lm_models.forward(params, tokens, shape)
    with jax.default_matmul_precision("highest"):
        ref, ref_loads = moonlight_fl.forward(
            moonlight_fl.unflatten(flat, cfg), tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(loads, ref_loads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    m = model.metrics(params, tokens, tokens)
    assert set(m) == {"ce", "accuracy", "expert_load_max"}
    assert float(m["expert_load_max"]) == pytest.approx(
        moonlight_fl.expert_load_max(ref_loads), rel=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Both 8-expert shares' routed outputs, plus the shared experts
    once, equal the layer holding all 16 experts."""
    full = _shape(n_held=16)
    params = lm_models.init_params(jax.random.PRNGKey(5), full)
    moe = params["layers"][1]["moe"]
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (T, full.hidden))
    ids, w = lm_models.route(x, moe["router"], full)
    whole, _ = lm_models.held_experts(x, ids, w, moe["experts"], full)
    parts = []
    for off in (0, 8):
        share = _shape(expert_offset=off)
        experts = jax.tree.map(lambda a, o=off: a[o:o + 8], moe["experts"])
        out, load = lm_models.held_experts(x, ids, w, experts, share)
        parts.append(out)
        assert int(jnp.sum(load)) == int(jnp.sum(
            (ids >= off) & (ids < off + 8)))
    shared = lm_models.swiglu(x, moe["shared"])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(whole + shared),
                               rtol=1e-5, atol=1e-6)
    assert int(jnp.sum(lm_models.held_experts(
        x, ids, w, moe["experts"], full)[1])) == T * full.top_k


def _system_round(S, backend, rounds=2, data_seed=1, seed=7):
    task, workers, _ = build_task_data("moonlight_lm", U=U, k_bar=K_BAR,
                                       data_seed=data_seed, seq_len=T,
                                       **SMALL)
    X, Y, mask, k_i = pad_workers(workers)
    cfg = FLConfig(rounds=rounds, lr=0.5, policy="inflota", k_b=1,
                   case=Case.GD_NONCONVEX, channel_model="exp_iid",
                   channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                   constants=LearningConstants(sigma2=1e-4),
                   backend=backend, worker_sharding=S)
    k_params, k_round = jax.random.split(jax.random.PRNGKey(seed))
    params0 = jax.eval_shape(task.init, k_params)
    eng = build_engine(task, X, Y, mask, k_i, cfg, params0)
    flat0, _ = ravel_pytree(task.init(k_params))
    st = eng.init(flat0, k_round)
    step = jax.jit(eng.step)
    stats = []
    for _ in range(rounds):
        st, s = step(st)
        stats.append(s)
    return np.asarray(st.flat), stats


def _reference_round(rounds=2, data_seed=1, seed=7):
    cfg = _ref_cfg(_shape())
    X, Y, mask, k_i = lm_data.lm_task(U, K_BAR, data_seed, cfg["vocab"],
                                      seq_len=T)
    ref = moonlight_fl.Round(cfg, X, Y, mask, k_i, lr=0.5, k_b=1,
                             sigma2=1e-4, p_max=10.0)
    k_params, k_round = jax.random.split(jax.random.PRNGKey(seed))
    flat0 = moonlight_fl.init_flat(k_params, cfg)
    start = np.asarray(flat0)
    state = [flat0, flat0 + 0.0, jnp.float32(0.0), jnp.int32(0), k_round]
    stats = []
    for _ in range(rounds):
        state, s = ref.step(state)
        stats.append(s)
    return np.asarray(state[0]), start, stats


@pytest.fixture(scope="module")
def reference_run():
    return _reference_round()


@pytest.mark.parametrize("S,backend", [(1, "jnp"), (2, "jnp"), (4, "jnp"),
                                       (4, "pallas")])
def test_sharded_round_equals_the_reference_round(reference_run, S,
                                                  backend):
    ref_flat, flat0, ref_stats = reference_run
    flat, stats = _system_round(S, backend)
    change = np.linalg.norm(ref_flat - flat0)
    assert change > 0
    assert np.linalg.norm(flat - ref_flat) <= 1e-4 * change
    names = {"selected": "selected", "b_mean": "b", "a_t": "a_t",
             "b_t": "b_t", "eta": "eta", "snr": "snr"}
    for s, r in zip(stats, ref_stats):
        for mine, theirs in names.items():
            np.testing.assert_allclose(float(getattr(s, mine)),
                                       float(r[theirs]), rtol=1e-4)


def test_dense_engine_refuses_the_task():
    task, workers, _ = build_task_data("moonlight_lm", U=U, k_bar=K_BAR,
                                       seq_len=T, **SMALL)
    X, Y, mask, k_i = pad_workers(workers)
    with pytest.raises(ValueError, match="worker_sharding"):
        build_engine(task, X, Y, mask, k_i, FLConfig(k_b=1),
                     task.init(jax.random.PRNGKey(0)))


def test_run_spec_runs_moonlight_into_a_store(tmp_path, monkeypatch):
    """The normal sweep path, at the small size: ``moonlight_lm`` from
    the task registry, worker-sharded, into a store; the history records
    the task's metrics, ``expert_load_max`` among them."""
    import functools

    from repro.data import tasks
    from repro.sweep import SweepSpec, run_spec
    from repro.sweep.store import SweepStore

    monkeypatch.setitem(
        tasks._TASK_REGISTRY, "moonlight_lm",
        functools.partial(tasks._TASK_REGISTRY["moonlight_lm"], seq_len=T,
                          **SMALL))
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"task": "moonlight_lm", "U": U, "k_bar": K_BAR,
                           "U_shards": U, "k_b": 1, "lr": 0.5,
                           "case": "gd_nonconvex", "rounds": 2,
                           "policy": "inflota", "channel": "exp_iid"})
    store = SweepStore(str(tmp_path / "store"))
    res = run_spec(spec, store=store)
    assert len(res) == 2 and all(r is not None for r in res)
    for r in res:
        hist = r["history"]
        assert len(hist["expert_load_max"]) == 2
        assert np.all(np.isfinite(hist["ce"]))
    assert len(store) == 2


def test_task_build_span_and_block_gauges(tmp_path):
    """``task.build`` records D and the bytes of one f32 D-vector; the
    worker-sharded engine sets its block gauges on the process
    registry, which the daemon's ``/metrics`` renders."""
    from repro.obs import metrics, trace

    trace.install(str(tmp_path))
    try:
        task, workers, _ = build_task_data("moonlight_lm", U=U, k_bar=K_BAR,
                                           seq_len=T, **SMALL)
        trace.flush()
        spans = [e for e in trace.load_events(str(tmp_path))
                 if e["name"] == "task.build"]
    finally:
        trace.uninstall()
    D = lm_models.param_count(_shape())
    assert [(s["args"]["D"], s["args"]["bytes"]) for s in spans] == [
        (D, 4 * D)]
    X, Y, mask, k_i = pad_workers(workers)
    build_engine(task, X, Y, mask, k_i, FLConfig(k_b=1, worker_sharding=2),
                 jax.eval_shape(task.init, jax.random.PRNGKey(0)))
    snap = metrics.process_registry().snapshot()
    assert snap["worker_shard_block_workers"] == U // 2
    assert snap["worker_shard_block_bytes"] == 4 * (U // 2) * D
    text = metrics.process_registry().render_prometheus()
    assert f"repro_worker_shard_block_workers {U // 2}" in text
