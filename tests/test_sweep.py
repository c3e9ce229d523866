"""Sweep-engine correctness: vmapped cohorts == sequential runs, store.

The load-bearing guarantee: a vectorized cohort of N experiments must be
BIT-EXACT against N sequential ``FLTrainer`` runs on the same backend —
the sweep engine is a pure execution-layout change, never a numerics
change.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.channel import ChannelConfig, ExpIID, ImperfectCSI
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data
from repro.fl.trainer import FLConfig, FLTrainer
from repro.runtime import compile_cache
from repro.sweep import SweepSpec, SweepStore, cell_hash, run_spec
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
