"""Async sweep runtime: scheduling is an execution-layout change, never
a numerics change.

The load-bearing guarantee mirrors the sweep engine's: ``jobs >= 2``
(concurrent dispatch + overlapped store I/O) and multi-host execution
must produce per-cell results IDENTICAL to the serial ``run_spec`` path
— same store hashes, same bytes — regardless of completion order.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.data.tasks import build_task_data
from repro.obs import metrics, trace
from repro.runtime import compile_cache
from repro.runtime import multihost as mh
from repro.runtime.scheduler import run_cohorts, schedule
from repro.runtime.writer import Completion, CompletionWriter
from repro.sweep import SweepSpec, SweepStore, cells, cohort_cost, \
    cohorts, grid, run_spec
from repro.sweep.grid import DEFAULTS, _cohort_batch, _ragged_uniques

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _float32_mode():
    """Async-vs-serial byte-identity compares against SUBPROCESS runs
    (default f32); other test modules flip the global x64 switch at
    import, which would change this process's trajectories."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


U, K_BAR, ROUNDS = 4, 6, 3


def _store_files(root):
    return {f: open(os.path.join(root, f), "rb").read()
            for f in sorted(os.listdir(root)) if f.endswith(".json")}


# --------------------------------------------------------------- scheduler

def test_schedule_costliest_first_deterministic():
    spec = SweepSpec(axes={"seed": (0, 1), "rounds": (2, 8, 4)},
                     base={"U": U, "k_bar": K_BAR})
    plan = cohorts(cells(spec))
    assert len(plan) == 3                       # rounds is a static field
    entries = schedule(plan)
    assert [e.cohort.static["rounds"] for e in entries] == [8, 4, 2]
    assert [e.cost for e in entries] == sorted(
        (cohort_cost(co) for co in plan), reverse=True)
    # equal-cost cohorts keep original order (reproducible plans)
    spec2 = SweepSpec(axes={"policy": ("inflota", "random")},
                      base={"U": U, "k_bar": K_BAR, "rounds": 2})
    assert [e.order for e in schedule(cohorts(cells(spec2)))] == [0, 1]


def test_async_matches_serial_on_mixed_grid(tmp_path):
    """Ragged (U) + scalar (sigma2) axes, several cohorts: the async
    path must reproduce the serial store byte-for-byte and every flat
    bit-for-bit, whatever order completions resolved in."""
    spec = SweepSpec(axes={"seed": (0, 1), "U": (4, 6),
                           "policy": ("inflota", "random"),
                           "sigma2": (1e-4, 1e-2)},
                     base={"k_bar": K_BAR, "rounds": ROUNDS,
                           "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 2
    serial = run_spec(spec, store=SweepStore(str(tmp_path / "serial")))
    asynced = run_spec(spec, jobs=2, dispatch_ahead=1,
                       store=SweepStore(str(tmp_path / "async")))
    assert len(serial) == len(asynced) == 16
    for s, a in zip(serial, asynced):
        assert s["cell"] == a["cell"]           # grid order preserved
        np.testing.assert_array_equal(s["flat"], a["flat"])
    assert _store_files(str(tmp_path / "serial")) == \
        _store_files(str(tmp_path / "async"))


def test_dispatch_error_propagates(monkeypatch):
    import repro.sweep.grid as grid_mod

    def boom(*a, **k):
        raise RuntimeError("prepare exploded")

    monkeypatch.setattr(grid_mod, "prepare_cohort", boom)
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS})
    with pytest.raises(RuntimeError, match="prepare exploded"):
        run_spec(spec, jobs=2)


def test_writer_error_propagates(tmp_path, monkeypatch):
    """A failing store write on the writer thread must fail the run on
    the caller's thread — not vanish into a daemon."""
    store = SweepStore(str(tmp_path))

    def bad_put(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(store, "put", bad_put)
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS})
    with pytest.raises(OSError, match="disk full"):
        run_spec(spec, jobs=2, store=store)


def test_run_cohorts_sink_called_once_per_cohort():
    spec = SweepSpec(axes={"seed": (0, 1), "rounds": (2, 3)},
                     base={"U": U, "k_bar": K_BAR}, eval=False)
    plan = cohorts(cells(spec))
    seen = []
    run_cohorts(plan, sink=lambda co, outs: seen.append((co, len(outs))),
                jobs=2, do_eval=False)
    assert sorted(n for _, n in seen) == [2, 2]
    assert {id(co) for co, _ in seen} == {id(co) for co in plan}


@pytest.mark.parametrize("jobs", [1, 2], ids=["serial", "scheduler"])
def test_repeated_grid_reuses_cohort_program(tmp_path, jobs,
                                             fresh_cohort_fns):
    """A second grid of the same spec on new seeds traces nothing, every
    dispatch reports a hit, and its results equal those of a run with
    the cache emptied: the serial path, and the scheduler's donating
    dispatch threads."""
    def spec(seeds):
        return SweepSpec(axes={"policy": ("inflota", "random"),
                               "seed": seeds},
                         base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS})

    run_spec(spec((0, 1)), jobs=jobs, dispatch_ahead=0)        # warm-up
    d = str(tmp_path / "t")
    trace.install(d)
    reg = metrics.Registry()
    warm = run_spec(spec((2, 3)), jobs=jobs, dispatch_ahead=0,
                    registry=reg)
    trace.uninstall()
    events = trace.load_events(d)
    assert not [e for e in events if e["name"] == "cohort.trace"]
    dispatches = [e for e in events if e["name"] == "cohort.dispatch"]
    assert len(dispatches) == 2
    assert all(e["args"]["hit"] is True for e in dispatches)
    snap = reg.snapshot()
    assert snap["cohort_fn_hits"] == 2 and "cohort_fn_misses" not in snap

    grid.cohort_fn_cache_clear()
    reg = metrics.Registry()
    cold = run_spec(spec((2, 3)), jobs=jobs, dispatch_ahead=0,
                    registry=reg)
    assert reg.snapshot()["cohort_fn_misses"] == 2
    for w, c in zip(warm, cold):
        assert w["cell"] == c["cell"]
        np.testing.assert_array_equal(w["flat"], c["flat"])
        assert w["history"].keys() == c["history"].keys()
        for k in w["history"]:
            np.testing.assert_array_equal(np.asarray(w["history"][k]),
                                          np.asarray(c["history"][k]))


# ------------------------------------------------------------------ writer

def test_writer_resolves_out_of_order():
    """A slow head-of-queue completion must not delay ready ones."""
    w = CompletionWriter(poll_interval=0.001)
    order = []
    gate = threading.Event()
    w.submit(Completion(label="slow", resolve=lambda: None,
                        sink=lambda v: order.append("slow"),
                        ready=gate.is_set))
    for name in ("fast1", "fast2"):
        w.submit(Completion(label=name, resolve=lambda: None,
                            sink=lambda v, n=name: order.append(n),
                            ready=lambda: True))
    deadline = time.time() + 10
    while len(order) < 2 and time.time() < deadline:
        time.sleep(0.005)
    assert order == ["fast1", "fast2"], order   # resolved past the head
    gate.set()
    w.close()
    assert w.drained() == ["fast1", "fast2", "slow"]


def test_writer_release_runs_after_error():
    """Window slots must come back even when sinks fail, or dispatchers
    would deadlock; only the first error surfaces."""
    w = CompletionWriter(poll_interval=0.001)
    released = []

    def sink(v):
        raise ValueError("sink failed")

    for i in range(3):
        w.submit(Completion(label=f"c{i}", resolve=lambda: None,
                            sink=sink, ready=lambda: True,
                            release=lambda i=i: released.append(i)))
    with pytest.raises(ValueError, match="sink failed"):
        w.close()
    assert sorted(released) == [0, 1, 2]


# ------------------------------------------------------- store concurrency

def test_store_put_atomic_and_merge(tmp_path):
    a = SweepStore(str(tmp_path / "a"))
    b = SweepStore(str(tmp_path / "b"))
    res = {"metrics": {"m": 1.0}, "history": {"m": [1.0]}}
    cell1 = dict(DEFAULTS, seed=1)
    cell2 = dict(DEFAULTS, seed=2)
    a.put(cell1, res)
    b.put(cell2, res)
    b.put(cell1, res)                      # overlapping entry
    assert a.merge(b) == 2
    assert len(a) == 2
    assert a.get(cell2)["metrics"]["m"] == 1.0

    # concurrent same-cell writers: the file is always a complete doc
    def hammer(i):
        for _ in range(10):
            a.put(cell1, {"metrics": {"m": float(i)}, "history": {}})

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert a.get(cell1)["metrics"]["m"] in {0.0, 1.0, 2.0, 3.0}
    assert not [f for f in os.listdir(a.root) if f.endswith(".tmp")]


# ----------------------------------------------------------- ragged dedup

def test_ragged_batch_dedups_shared_datasets():
    """8 cells over 2 unique datasets must hold 2 padded copies, not 8 —
    each experiment carries only an index into the unique stack."""
    spec = SweepSpec(axes={"seed": (0, 1, 2, 3), "U": (4, 6)},
                     base={"k_bar": K_BAR, "rounds": 2})
    (co,) = cohorts(cells(spec))
    assert co.ragged and len(co) == 8
    built = {key: build_task_data(key[0], U=key[1], k_bar=key[2],
                                  data_seed=key[3])
             for key in co.data_keys()}
    uniques, batch_eval = _ragged_uniques(co, built, True, None)
    batch = _cohort_batch(co, {})
    assert batch["didx"].shape == (8,)
    assert sorted(set(np.asarray(batch["didx"]).tolist())) == [0, 1]
    assert uniques["X"].shape[0] == 2          # unique datasets only
    assert uniques["X"].shape[1] == 6          # padded to U_max
    assert batch_eval and uniques["ex"].shape[0] == 2


# -------------------------------------------------------- bound histories

def test_history_carries_realized_bound_terms():
    """Every run's history reports the realized Lemma-1 terms, so
    convergence bounds are assertable cohort-wide (theory_check)."""
    spec = SweepSpec(axes={"seed": (0,)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS})
    (res,) = run_spec(spec)
    a_seq = np.asarray(res["history"]["a_t"])
    b_seq = np.asarray(res["history"]["b_t"])
    assert a_seq.shape == b_seq.shape == (ROUNDS,)
    assert np.all(b_seq > 0)                  # noise makes B_t positive
    assert {"a_t_final", "a_t_tail", "b_t_final",
            "b_t_tail"} <= set(res["metrics"])


def test_async_sharded_matches_serial():
    """4 forced host devices: mesh-sharded + jobs=2 == plain serial.

    Subprocess because XLA_FLAGS must be set before jax initializes.
    """
    prog = r"""
import numpy as np
import jax
jax.config.update("jax_platform_name", "cpu")
assert len(jax.devices()) == 4, jax.devices()
from repro.sweep import SweepSpec, run_spec
from repro.sweep import shard as shard_lib
spec = SweepSpec(axes={"seed": (0, 1, 2, 3, 4, 5)},
                 base={"U": 5, "k_bar": 8, "rounds": 4, "backend": "jnp"})
plain = run_spec(spec)
sharded = run_spec(spec, mesh=shard_lib.sweep_mesh(), jobs=2)
for a, b in zip(plain, sharded):
    np.testing.assert_array_equal(np.asarray(a["flat"]),
                                  np.asarray(b["flat"]))
print("ASYNC-SHARD-OK")
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
    assert "ASYNC-SHARD-OK" in out.stdout


# --------------------------------------------------------------- multihost

def test_partition_balanced_and_deterministic():
    spec = SweepSpec(axes={"seed": (0, 1), "rounds": (2, 4, 8, 16)},
                     base={"U": U, "k_bar": K_BAR})
    plan = cohorts(cells(spec))
    parts = mh.partition(plan, 2)
    assert parts == mh.partition(plan, 2)      # deterministic
    assert sorted(i for p in parts for i in p) == list(range(len(plan)))
    loads = [sum(cohort_cost(plan[i]) for i in p) for p in parts]
    # LPT puts rounds=16 alone vs {8,4,2} together: loads 16r vs 14r
    assert max(loads) / sum(loads) < 0.6
    with pytest.raises(ValueError):
        mh.HostSpec(num_hosts=2, host_id=2)


def test_wait_for_hosts_rejects_stale_sentinels(tmp_path):
    """A sentinel from a previous launch (different plan signature) must
    read as 'host not finished', not as a completed host."""
    root = str(tmp_path)
    with open(mh._sentinel(root, 1), "w") as f:
        json.dump({"host": 1, "cells": 4, "plan": "deadbeef"}, f)
    with pytest.raises(TimeoutError, match="hosts \\[1\\]"):
        mh._wait_for_hosts(root, {1: "cafe1234"}, timeout=0.3)
    with open(mh._sentinel(root, 1), "w") as f:
        json.dump({"host": 1, "cells": 4, "plan": "cafe1234"}, f)
    done = mh._wait_for_hosts(root, {1: "cafe1234"}, timeout=5)
    assert done[1]["cells"] == 4


def test_multihost_single_host_inprocess(tmp_path):
    spec = SweepSpec(axes={"seed": (0, 1), "policy": ("inflota",
                                                      "random")},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS})
    res = mh.run_spec_multihost(spec, store_root=str(tmp_path),
                                hs=mh.HostSpec(), jobs=2)
    assert len(res) == 4
    assert os.path.exists(tmp_path / "host0.done")
    merged = SweepStore(str(tmp_path))
    assert len(merged) == 4
    # a second launch is served entirely from the merged root store
    res2 = mh.run_spec_multihost(spec, store_root=str(tmp_path),
                                 hs=mh.HostSpec(), jobs=2)
    assert json.load(open(tmp_path / "host0.done"))["cells"] == 0
    for a, b in zip(res, res2):
        assert a["metrics"] == pytest.approx(b["metrics"])


def test_multihost_two_process_jax_distributed(tmp_path):
    """2-process ``jax.distributed`` smoke test: both hosts run their
    cohort slice, host 0 merges, and the merged store is byte-identical
    to a serial in-process run.  Skips when the distributed runtime is
    unavailable in this environment."""
    spec = SweepSpec(axes={"seed": (0, 1, 2), "policy": ("inflota",
                                                         "random")},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS,
                           "backend": "jnp"})
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    root = str(tmp_path / "mh")
    prog = r"""
import sys
import jax
jax.config.update("jax_platform_name", "cpu")
from repro.sweep import SweepSpec
from repro.runtime import multihost as mh
host_id = int(sys.argv[1])
spec = SweepSpec(axes={"seed": (0, 1, 2),
                       "policy": ("inflota", "random")},
                 base={"U": %d, "k_bar": %d, "rounds": %d,
                       "backend": "jnp"})
res = mh.run_spec_multihost(
    spec, store_root=sys.argv[2],
    hs=mh.HostSpec(num_hosts=2, host_id=host_id,
                   coordinator="localhost:%d"),
    jobs=2, timeout=240)
if host_id == 0:
    assert res is not None and len(res) == 6, res
    print("MH-OK", len(res))
else:
    assert res is None
""" % (U, K_BAR, ROUNDS, port)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    procs = [subprocess.Popen([sys.executable, "-c", prog, str(h), root],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for h in (0, 1)]
    try:
        outs = [p.communicate(timeout=280) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("jax.distributed 2-process run timed out here")
    if any(p.returncode != 0 for p in procs):
        err = "\n".join(o[1][-1500:] for o in outs)
        if "MH-OK" not in outs[0][0]:
            pytest.skip(f"jax.distributed unsupported here: {err[-500:]}")
    assert "MH-OK 6" in outs[0][0], outs[0]

    # merged root store == serial in-process store, byte for byte
    serial_dir = str(tmp_path / "serial")
    run_spec(spec, store=SweepStore(serial_dir))
    assert _store_files(root) == _store_files(serial_dir)


# --------------------------------------------------------------------- cli

def test_cli_dry_run_prints_schedule(tmp_path, capsys):
    from repro.sweep.cli import main
    rc = main(["--task", "linreg", "--U", str(U), "--k-bar", str(K_BAR),
               "--rounds", "3", "--axis", "seed=0:2",
               "--axis", "policy=inflota,random",
               "--jobs", "2", "--num-hosts", "2", "--dry-run"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "# schedule: jobs=2" in err
    assert "dispatch order:" in err
    assert "host 0: cohorts" in err and "host 1: cohorts" in err


def test_cli_jobs_end_to_end(tmp_path, monkeypatch):
    from repro.sweep.cli import main
    # main() would turn the persistent compile cache on in this worker
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    serial_dir, async_dir = str(tmp_path / "s"), str(tmp_path / "a")
    args = ["--task", "linreg", "--U", str(U), "--k-bar", str(K_BAR),
            "--rounds", "3", "--axis", "seed=0:2",
            "--axis", "policy=inflota,random", "-q",
            "--csv", str(tmp_path / "out.csv")]
    assert main(args + ["--store", serial_dir]) == 0
    assert main(args + ["--store", async_dir, "--jobs", "2"]) == 0
    assert _store_files(serial_dir) == _store_files(async_dir)


def test_compile_cache_dir(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and the helper sets nothing;
    otherwise one fixed directory inside the checkout, every call."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable() == want
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
