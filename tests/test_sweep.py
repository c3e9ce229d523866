"""Sweep-engine correctness: vmapped cohorts == sequential runs, store.

The load-bearing guarantee: a vectorized cohort of N experiments must be
BIT-EXACT against N sequential ``FLTrainer`` runs on the same backend —
the sweep engine is a pure execution-layout change, never a numerics
change.
"""

import contextlib
import dataclasses
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import selection as sel
from repro.core.channel import ChannelConfig, ExpIID, ImperfectCSI
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data
from repro.fl.trainer import FLConfig, FLTrainer
from repro.obs import trace
from repro.runtime import compile_cache
from repro.sweep import SweepSpec, SweepStore, cell_hash, grid, run_spec
from repro.sweep import shard as shard_lib
from repro.sweep.grid import DEFAULTS, cells, cohorts, result_by
from repro.sweep.store import canonical_cell, long_rows

jax.config.update("jax_platform_name", "cpu")

U, K_BAR, ROUNDS = 6, 10, 8


def _sequential(cell, task, workers, test):
    cfg = FLConfig(rounds=cell["rounds"], lr=cell["lr"],
                   policy=cell["policy"], case=Case.GD_CONVEX,
                   channel=ChannelConfig(sigma2=cell["sigma2"],
                                         p_max=cell["p_max"]),
                   channel_model=cell["channel"],
                   constants=LearningConstants(sigma2=cell["sigma2"]),
                   backend="jnp", scan=True)
    h = FLTrainer(task, workers, cfg).run(
        key=jax.random.PRNGKey(cell["seed"]), eval_data=test)
    return h, np.asarray(ravel_pytree(h["params"])[0])


@pytest.mark.parametrize("policy", ["inflota", "random"])
@pytest.mark.parametrize("channel", [None, "gauss_markov"])
def test_cohort_bitexact_vs_sequential(policy, channel):
    """N-seed vmapped cohort == N sequential FLTrainer runs, bit-for-bit,
    including the stateful Gauss-Markov carry threading."""
    spec = SweepSpec(axes={"seed": (0, 1, 2)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS,
                           "policy": policy, "channel": channel,
                           "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1    # one compile for all seeds
    results = run_spec(spec)
    task, workers, test = build_task_data("linreg", U=U, k_bar=K_BAR,
                                          data_seed=0)
    for r in results:
        h, flat = _sequential(r["cell"], task, workers, test)
        np.testing.assert_array_equal(flat, r["flat"])
        np.testing.assert_array_equal(np.asarray(h["mse"]),
                                      np.asarray(r["history"]["mse"]))
        np.testing.assert_array_equal(np.asarray(h["selected"]),
                                      np.asarray(r["history"]["selected"]))


def test_vector_scalar_axis_one_cohort():
    """sigma2 varies WITHIN one cohort (traced operand, single compile)
    and each cell still matches its sequential twin."""
    spec = SweepSpec(axes={"sigma2": (1e-4, 1e-2, 1e-1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS,
                           "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1
    results = run_spec(spec)
    task, workers, test = build_task_data("linreg", U=U, k_bar=K_BAR,
                                          data_seed=0)
    for r in results:
        _, flat = _sequential(r["cell"], task, workers, test)
        np.testing.assert_allclose(flat, r["flat"], rtol=1e-6, atol=0)


def test_static_axes_partition_cohorts():
    spec = SweepSpec(axes={"seed": (0, 1), "policy": ("inflota", "random"),
                           "U": (4, 6)},
                     base={"k_bar": K_BAR, "rounds": 2})
    cl = cells(spec)
    assert len(cl) == 8
    cos = cohorts(cl)
    assert len(cos) == 2                       # policy splits; U is ragged
    assert all(len(c) == 4 for c in cos)       # seeds + U ride together
    assert all(c.ragged for c in cos)
    # grid order is preserved through cohort execution order bookkeeping
    assert sorted(i for c in cos for i in c.indices) == list(range(8))
    # the pre-ragged partitioning is still reachable (before/after bench)
    legacy = cohorts(cl, legacy=True)
    assert len(legacy) == 4                    # policy x U static split
    assert not any(c.ragged for c in legacy)


def test_ragged_exclusions_stay_shape_exact():
    """Channels whose numerics depend on the padded worker-axis extent
    (``ragged_exact = False``, e.g. ensemble-normalized pathloss) must
    not ragged-merge.  Minibatch (k_b) cells DO merge now: the
    per-sample ``fold_in`` sampler and the k_i>0 worker count made their
    draws restriction-stable (ISSUE 6)."""
    spec = SweepSpec(axes={"U": (4, 6)},
                     base={"k_bar": K_BAR, "rounds": 2, "k_b": 4})
    assert len(cohorts(cells(spec))) == 1
    spec = SweepSpec(axes={"U": (4, 6)},
                     base={"k_bar": K_BAR, "rounds": 2,
                           "channel": "pathloss"})
    assert len(cohorts(cells(spec))) == 2
    # ... and the default channel merges as before
    spec = SweepSpec(axes={"U": (4, 6)}, base={"k_bar": K_BAR, "rounds": 2})
    assert len(cohorts(cells(spec))) == 1


def test_unknown_field_rejected():
    with pytest.raises(ValueError, match="unknown cell field"):
        SweepSpec(axes={"nope": (1, 2)})
    with pytest.raises(ValueError, match="empty axis"):
        SweepSpec(axes={"seed": ()})


# ------------------------------------------------------------------- store

def test_cell_hash_stable_and_discriminating():
    a = dict(DEFAULTS, seed=3, policy="inflota")
    # insertion order must not matter
    b = {k: a[k] for k in reversed(list(a))}
    assert cell_hash(a) == cell_hash(b)
    assert cell_hash(a) != cell_hash(dict(a, seed=4))
    # structured values canonicalize by class + fields
    m1 = dict(a, channel=ImperfectCSI(ExpIID(u=6), eps=0.1))
    m2 = dict(a, channel=ImperfectCSI(ExpIID(u=6), eps=0.1))
    m3 = dict(a, channel=ImperfectCSI(ExpIID(u=6), eps=0.2))
    assert cell_hash(m1) == cell_hash(m2) != cell_hash(m3)
    assert "ImperfectCSI" in canonical_cell(m1)


def test_store_roundtrip_and_cache_hit(tmp_path, monkeypatch):
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": 4})
    store = SweepStore(str(tmp_path))
    first = run_spec(spec, store=store)
    assert len(store) == 2

    # a second run must be served entirely from the store: executing any
    # cohort would call run_cohort, which we break on purpose
    import repro.sweep.grid as grid_mod

    def boom(*a, **k):
        raise AssertionError("cache miss: run_cohort executed")

    monkeypatch.setattr(grid_mod, "run_cohort", boom)
    second = run_spec(spec, store=store)
    for f, s in zip(first, second):
        assert f["metrics"] == pytest.approx(s["metrics"])
        assert s["cell"]["seed"] == f["cell"]["seed"]

    # any config change misses the cache again
    changed = SweepSpec(axes={"seed": (0, 1)},
                        base={"U": U, "k_bar": K_BAR, "rounds": 5})
    with pytest.raises(AssertionError, match="cache miss"):
        run_spec(changed, store=store)


def test_store_key_covers_eval_settings(tmp_path):
    """A --no-eval run must not satisfy a later metrics-wanting run, and
    eval_data overrides are refused with a store (cache poisoning)."""
    store = SweepStore(str(tmp_path))
    base = {"U": U, "k_bar": K_BAR, "rounds": 3}
    run_spec(SweepSpec(axes={"seed": (0,)}, base=base, eval=False),
             store=store)
    with_eval = SweepSpec(axes={"seed": (0,)}, base=base)
    results = run_spec(with_eval, store=store)
    assert "mse_tail" in results[0]["metrics"]   # NOT the cached no-eval
    assert len(store) == 2                       # distinct cache entries
    # a different tail window is a distinct entry too
    run_spec(SweepSpec(axes={"seed": (0,)}, base=base, tail=2),
             store=store)
    assert len(store) == 3
    task_data = build_task_data("linreg", U=U, k_bar=K_BAR, data_seed=0)
    with pytest.raises(ValueError, match="mutually exclusive"):
        run_spec(with_eval, store=store, eval_data=task_data[2])


def test_long_rows_tidy_format():
    spec = SweepSpec(axes={"seed": (0,)},
                     base={"U": U, "k_bar": K_BAR, "rounds": 3})
    rows = long_rows(run_spec(spec), columns=["seed", "policy"])
    assert {r["metric"] for r in rows} >= {"mse_final", "mse_tail",
                                           "selected_mean"}
    assert all(set(r) == {"seed", "policy", "metric", "value"}
               for r in rows)


def test_result_by_unique_match():
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": 2},
                     eval=False)
    results = run_spec(spec)
    assert result_by(results, seed=1)["cell"]["seed"] == 1
    with pytest.raises(ValueError, match="2 results"):
        result_by(results, policy="inflota")


# ------------------------------------------------------ cohort programs

def _cohort_of(seeds=(0, 1), lr=0.1, data_seed=0, policy="inflota"):
    axes = {"seed": seeds}
    if isinstance(lr, tuple):
        axes["lr"] = lr
    base = {"U": U, "k_bar": K_BAR, "rounds": 3, "data_seed": data_seed,
            "backend": "jnp", "policy": policy}
    if not isinstance(lr, tuple):
        base["lr"] = lr
    (co,) = cohorts(cells(SweepSpec(axes=axes, base=base)))
    return co


def _dispatch(co, **kw):
    """Prepare and run one cohort: (hit, host output)."""
    prep = grid.prepare_cohort(co, **kw)
    out, e, hit = grid.dispatch_cohort(prep)
    return hit, shard_lib.resolve(out, e)


def _held_out():
    return build_task_data("linreg", U=U, k_bar=K_BAR, data_seed=7)[2]


@pytest.mark.parametrize("change, traces", [
    ("seeds", False), ("uniform_lr", True), ("data_seed", True),
    ("do_eval", True), ("eval_data", True), ("lr_varies", True),
    ("x64", True)])
def test_cohort_program_retraces_when_its_trace_inputs_change(
        tmp_path, change, traces, fresh_cohort_fns):
    """A cohort that differs from a dispatched one only in its seeds
    reuses its program; any other trace input traces anew."""
    co, kw = {
        "seeds": (_cohort_of(seeds=(5, 6)), {}),
        "uniform_lr": (_cohort_of(lr=0.05), {}),
        "data_seed": (_cohort_of(data_seed=1), {}),
        "do_eval": (_cohort_of(), {"do_eval": False}),
        "eval_data": (_cohort_of(), {"eval_data": _held_out()}),
        "lr_varies": (_cohort_of(lr=(0.1, 0.05)), {}),
        "x64": (_cohort_of(), {}),
    }[change]
    mode = (jax.enable_x64(not jax.config.jax_enable_x64)
            if change == "x64" else contextlib.nullcontext())
    _dispatch(_cohort_of())
    d = str(tmp_path / "t")
    trace.install(d)
    with mode:                         # the data are built in this mode
        hit, _ = _dispatch(co, **kw)
    trace.uninstall()
    n = len([e for e in trace.load_events(d) if e["name"] == "cohort.trace"])
    assert (hit, n) == ((False, 1) if traces else (True, 0))


def test_cohort_program_keys_eval_data_by_content(fresh_cohort_fns):
    ex, ey = _held_out()
    _dispatch(_cohort_of(), eval_data=(ex, ey))
    assert _dispatch(_cohort_of(), eval_data=(ex.copy(), ey.copy()))[0]
    assert not _dispatch(_cohort_of(), eval_data=(ex, ey + 1.0))[0]


def _policy_class(top_half):
    """A policy class of the same name and fields whatever its body: every
    worker transmits, or those with the better half of the channels."""
    @dataclasses.dataclass(frozen=True)
    class SketchPolicy(sel.RoundPolicyBase):
        def decide(self, key, ctx):
            D = ctx.w_prev_abs.shape[0]
            beta = ((ctx.h_est >= jnp.median(ctx.h_est)) if top_half
                    else jnp.ones_like(ctx.h_est)).astype(jnp.float32)
            return sel.make_decision(jnp.ones((D,)), beta[:, None],
                                     ctx.k_eff, ctx.k_i, wmask=ctx.wmask)
    return SketchPolicy


@pytest.mark.parametrize("given", ["instance", "name"])
def test_cohort_program_tells_redefined_policies_apart(
        tmp_path, monkeypatch, given, fresh_cohort_fns):
    """Two policies of one class name and equal fields but different
    ``decide`` bodies (a class defined anew, or a name registered anew)
    each trace, and each cohort gives what it gives on an empty cache."""
    def policy(top_half):
        cls = _policy_class(top_half)
        if given == "instance":
            return cls()
        monkeypatch.setitem(sel._POLICY_REGISTRY, "sketch",
                            lambda **kw: cls())
        return "sketch"

    outs = []
    for top_half in (False, True):
        co = _cohort_of(policy=policy(top_half))
        d = str(tmp_path / f"t{top_half}")
        trace.install(d)
        hit, out = _dispatch(co)
        trace.uninstall()
        assert not hit
        assert [e["name"] for e in trace.load_events(d)
                if e["name"] == "cohort.trace"] == ["cohort.trace"]
        outs.append(out)
    grid.cohort_fn_cache_clear()
    alone = _dispatch(co)[1]
    for k in alone:
        np.testing.assert_array_equal(np.asarray(outs[1][k]),
                                      np.asarray(alone[k]))
    assert not np.array_equal(np.asarray(outs[0]["selected"]),
                              np.asarray(outs[1]["selected"]))


@pytest.mark.parametrize("bound", ["entries", "bytes"])
def test_cohort_program_cache_evicts_least_recent(monkeypatch, bound,
                                                  fresh_cohort_fns):
    """Past its bound in programs, or in bytes of the data they hold,
    the cache drops the least recently used program."""
    def program(lr):
        return grid.prepare_cohort(_cohort_of(lr=lr)).program

    a = program(0.1)
    if bound == "entries":
        monkeypatch.setattr(grid, "COHORT_FN_CACHE_SIZE", 2)
    else:                                      # room for two programs
        monkeypatch.setattr(grid, "COHORT_FN_CACHE_BYTES", 2 * a.nbytes)
    b = program(0.2)
    assert program(0.1) is a                   # a is now the most recent
    program(0.3)                               # evicts b
    assert grid.cohort_fn_cache_info()["currsize"] == 2
    assert program(0.1) is a
    assert program(0.2) is not b


def test_cohort_program_bytes_count_each_compiled_copy(monkeypatch,
                                                       fresh_cohort_fns):
    """A program holds its data once, and once more for each batch shape
    it compiled; one larger than the byte bound runs but is not kept."""
    def nbytes():
        return grid.cohort_fn_cache_info()["nbytes"]

    prep = grid.prepare_cohort(_cohort_of())
    data = prep.program.nbytes
    assert data > 0 and nbytes() == data
    assert not grid.dispatch_cohort(prep)[2]
    assert nbytes() == 2 * data
    assert _dispatch(_cohort_of(seeds=(4, 5)))[0]    # same shapes
    assert nbytes() == 2 * data
    assert not _dispatch(_cohort_of(seeds=(0, 1, 2)))[0]
    assert nbytes() == 3 * data

    grid.cohort_fn_cache_clear()
    monkeypatch.setattr(grid, "COHORT_FN_CACHE_BYTES", data - 1)
    hit, out = _dispatch(_cohort_of())
    assert not hit and out["selected"].shape[0] == 2
    assert grid.cohort_fn_cache_info()["currsize"] == 0
    assert not _dispatch(_cohort_of())[0]      # built anew


def test_threads_dispatching_one_program_get_their_own_results(
        fresh_cohort_fns):
    """Two threads prepare and dispatch cohorts of one trace key at once;
    each gets what a lone run of its cohort gives."""
    seed_sets = [(0, 1), (2, 3), (4, 5), (6, 7)]
    alone = {}
    for seeds in seed_sets:
        grid.cohort_fn_cache_clear()
        alone[seeds] = _dispatch(_cohort_of(seeds=seeds))[1]
    grid.cohort_fn_cache_clear()
    got, errors = {}, []
    barrier = threading.Barrier(2)

    def work(mine):
        try:
            barrier.wait(timeout=30)
            for seeds in mine:
                got[seeds] = _dispatch(_cohort_of(seeds=seeds))[1]
        except Exception as e:                 # noqa: BLE001 — reported
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(seed_sets[i::2],))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert grid.cohort_fn_cache_info()["currsize"] == 1
    for seeds in seed_sets:
        for k in alone[seeds]:
            np.testing.assert_array_equal(np.asarray(got[seeds][k]),
                                          np.asarray(alone[seeds][k]))


# ---------------------------------------------------------------- sharding

def test_shard_pad_unpad_roundtrip():
    from repro.sweep import shard as shard_lib
    batch = {"key": np.arange(10).reshape(5, 2), "lr": np.arange(5.0)}
    padded, e = shard_lib.pad_batch(batch, 4)
    assert e == 5
    assert padded["key"].shape == (8, 2)
    # padding repeats the trailing experiment (valid, discarded later)
    np.testing.assert_array_equal(
        padded["key"][5:], np.tile(batch["key"][4:5], (3, 1)))
    out = shard_lib.unpad(padded, e)
    np.testing.assert_array_equal(out["lr"], batch["lr"])
    assert shard_lib.sweep_mesh(1) is None     # degrades to no-op


def test_sharded_run_matches_unsharded():
    """4 forced host devices: mesh-sharded cohort == single-device cohort.

    Subprocess because XLA_FLAGS must be set before jax initializes.
    """
    prog = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platform_name", "cpu")
assert len(jax.devices()) == 4, jax.devices()
from repro.sweep import SweepSpec, run_spec
from repro.sweep import shard as shard_lib
spec = SweepSpec(axes={"seed": (0, 1, 2, 3, 4, 5)},
                 base={"U": 5, "k_bar": 8, "rounds": 4, "backend": "jnp"})
plain = run_spec(spec)
mesh = shard_lib.sweep_mesh()
assert mesh is not None and shard_lib.shard_count(mesh) == 4
sharded = run_spec(spec, mesh=mesh)
for a, b in zip(plain, sharded):
    np.testing.assert_array_equal(np.asarray(a["flat"]),
                                  np.asarray(b["flat"]))
print("SHARD-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARD-OK" in out.stdout


# --------------------------------------------------------------------- cli

def test_cli_end_to_end(tmp_path, capsys, monkeypatch):
    from repro.sweep.cli import main, parse_axis
    # main() would turn the persistent compile cache on in this worker
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    assert parse_axis("seed=0:3") == ("seed", [0, 1, 2])
    assert parse_axis("policy=inflota,random") == (
        "policy", ["inflota", "random"])
    assert parse_axis("channel=none,gauss_markov") == (
        "channel", [None, "gauss_markov"])
    store_dir = tmp_path / "store"
    csv = tmp_path / "out.csv"
    rc = main(["--task", "linreg", "--U", str(U), "--k-bar", str(K_BAR),
               "--rounds", "3", "--axis", "seed=0:2",
               "--store", str(store_dir), "--csv", str(csv), "-q"])
    assert rc == 0
    assert len(list(store_dir.glob("*.json"))) == 2
    header = csv.read_text().splitlines()[0]
    assert header == "seed,metric,value"


def test_run_py_only_accepts_comma_list():
    import argparse
    from benchmarks.run import SECTIONS, parse_only
    ap = argparse.ArgumentParser()
    assert parse_only("fig4_5_6,csi", ap) == ["fig4_5_6", "csi"]
    assert parse_only(None, ap) == list(SECTIONS)
    with pytest.raises(SystemExit):
        parse_only("fig4_5_6,nope", ap)
