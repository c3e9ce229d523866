"""Sweep-engine throughput + single-compile cohort merging.

Section 1 — the ISSUE-3 acceptance grid: 8 seeds x 2 policies x 2
channels (linreg, ``scan=True``), driven two ways over the SAME cells —

  sequential:  one fresh ``FLTrainer`` per cell, exactly how the fig
               benchmarks drove grids before the sweep engine (every run
               re-traces + re-compiles + round-trips the host);
  vectorized:  ``repro.sweep.run_spec`` — one jitted, vmapped, device-
               resident computation per (policy x channel) cohort.

Reports runs/sec for both, the speedup, and a bit-exactness count (every
vectorized cell must match its sequential twin's final parameters
bit-for-bit).

Section 2 — the ISSUE-4 cohort-merge comparison: the fig4_5_6 benchmark
grids plus the U x eps x sigma2 acceptance grid, partitioned BEFORE
(``cohorts(..., legacy=True)``: U / k_bar / eps static, one compile per
combination) and AFTER (ragged worker padding + traced eps/rho/sigma2/L:
one compile per shape family).  Both plans execute the same cells;
``compile_s`` / ``run_s`` split trace+compile wall time from
post-compile execution, so the committed numbers show exactly what the
merge buys.  The split is read from the system's own spans: a JSONL
trace recorder (``repro.obs.trace``) in a temporary directory records
each cohort's ``cohort.dispatch`` (trace, lower, compile, enqueue:
``compile_s``) and ``cohort.resolve`` (wait for the device, fetch,
finalize: ``run_s``).  ``--json`` writes the committed
``BENCH_sweeps.json``.

Section 3 — the ISSUE-5 serial-vs-async runtime comparison.  Two
workloads, each driven twice over identical cohort computations with
store writes included:

  serial:  the legacy loop — trace, compile, execute, fetch, store-write
           one cohort at a time;
  async:   ``repro.runtime`` with ``jobs=2`` — cohorts dispatch
           concurrently (costliest first), device compute overlaps the
           next cohort's trace/compile, and a background writer thread
           drains fetch + store I/O.

The fig4_5_6 workload is all three figure grids' cohorts through one
scheduler session at paper-length rounds (the win comes from overlapping
execution, Python-side tracing, and store I/O with the GIL-free compile
stream); the mlp workload has real per-round FLOPs, so device execution
itself overlaps the other cohort's compile.  Committed walls are MEDIANS
over 3 runs per layout (single compile walls vary more here than the
overlap win).  Every async cell must match its serial twin bit-for-bit —
scheduling is an execution-layout change, never a numerics change.
"""

from __future__ import annotations

import argparse
import json
import platform
import statistics
import tempfile
import time

import jax
import numpy as np
from jax.flatten_util import ravel_pytree

from benchmarks import common
from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data
from repro.fl.trainer import FLConfig, FLTrainer
from repro.obs import trace
from repro.sweep import SweepSpec, SweepStore, run_spec, spec_cache_key
from repro.sweep.grid import cells, cohorts, run_cohort

SEEDS = 8
POLICIES = ("inflota", "random")
CHANNELS = (None, "gauss_markov")
U, K_BAR = 20, 30


def _spec(rounds: int) -> SweepSpec:
    return SweepSpec(axes={"policy": POLICIES, "channel": CHANNELS,
                           "seed": tuple(range(SEEDS))},
                     base={"U": U, "k_bar": K_BAR, "rounds": rounds,
                           "lr": 0.1, "backend": "jnp"},
                     eval=False)


def _sequential(rounds: int):
    """One fresh FLTrainer per cell (the pre-sweep benchmark pattern)."""
    task, workers, _ = build_task_data("linreg", U=U, k_bar=K_BAR,
                                       data_seed=0)
    flats = []
    for cell in cells(_spec(rounds)):
        cfg = FLConfig(rounds=rounds, lr=0.1, policy=cell["policy"],
                       case=Case.GD_CONVEX,
                       channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                       channel_model=cell["channel"],
                       constants=LearningConstants(sigma2=1e-4),
                       backend="jnp", scan=True)
        h = FLTrainer(task, workers, cfg).run(
            key=jax.random.PRNGKey(cell["seed"]))
        flats.append(np.asarray(ravel_pytree(h["params"])[0]))
    return flats


def _fig_specs(rounds: int) -> dict[str, SweepSpec]:
    """The three fig4_5_6 benchmark grids (no-eval: these comparisons
    time training compute, not metric evaluation)."""
    figs = {"U": (5, 10, 20, 40), "k_bar": (10, 20, 40, 80),
            "sigma2": (1e-4, 1e-2, 1e-1, 1.0)}
    base = {"rounds": rounds, "lr": 0.1, "backend": "jnp"}
    return {ax: SweepSpec(axes={ax: vals, "policy": ("inflota", "random")},
                          base=dict(base), eval=False)
            for ax, vals in figs.items()}


def _merge_specs(rounds: int) -> dict[str, SweepSpec]:
    """The grids whose cohort plans the merge changes."""
    out = {f"fig4_5_6[{ax}]": spec
           for ax, spec in _fig_specs(rounds).items()}
    out["u_eps_sigma2"] = SweepSpec(
        axes={"U": (5, 10, 20), "eps": (0.0, 0.1),
              "sigma2": (1e-4, 1e-2)},
        base={"rounds": rounds, "lr": 0.1, "backend": "jnp",
              "k_bar": 20, "channel": "exp_iid_csi"}, eval=False)
    return out


def _run_plan(spec: SweepSpec, legacy: bool) -> dict[str, float]:
    """Execute a spec under one cohort plan, timing compile vs run from
    the cohorts' ``cohort.dispatch`` and ``cohort.resolve`` spans."""
    cl = cells(spec)
    plan = cohorts(cl, legacy=legacy)
    with tempfile.TemporaryDirectory() as d:
        trace.install(d)
        try:
            for order, co in enumerate(plan):
                run_cohort(co, do_eval=False, order=order)
        finally:
            trace.uninstall()
        events = trace.load_events(d)

    def seconds(name):
        return sum(e["dur"] for e in events if e["name"] == name) / 1e6

    return {"cells": len(cl), "cohorts": len(plan),
            "compile_s": seconds("cohort.dispatch"),
            "run_s": seconds("cohort.resolve")}


def cohort_merge_rows(rounds: int = 40):
    """Before/after cohort counts + compile/run walls per grid."""
    rows = []
    for name, spec in _merge_specs(rounds).items():
        for tag, legacy in (("before", True), ("after", False)):
            jax.clear_caches()      # each plan pays its own compiles
            r = _run_plan(spec, legacy)
            rps = r["cells"] / (r["compile_s"] + r["run_s"])
            rows.append({
                "name": f"cohorts_{name}_{tag}",
                "metric": "cells/cohorts/compile_s/runs_per_s",
                "value": [r["cells"], r["cohorts"],
                          round(r["compile_s"], 2), round(rps, 3)]})
    return rows


def _serial_cohorts(workload, store: SweepStore):
    """The legacy execution layout: one cohort at a time, store writes on
    the dispatch path.  ``workload`` is [(spec, cohort), ...]; returns
    {grid index within its spec: flat params} keyed per (spec id, idx)."""
    flats = {}
    for spec, co in workload:
        for idx, res in zip(co.indices, run_cohort(co, do_eval=False,
                                                   tail=spec.tail)):
            store.put(res["cell"], res, spec_cache_key(spec))
            flats[(id(spec), idx)] = np.asarray(res["flat"])
    return flats


def _async_cohorts(workload, store: SweepStore, jobs: int):
    """The same cohort computations through the async runtime."""
    from repro.runtime import scheduler as sched_lib
    owner = {id(co): spec for spec, co in workload}
    flats = {}

    def sink(co, outs):
        spec = owner[id(co)]
        for idx, res in zip(co.indices, outs):
            store.put(res["cell"], res, spec_cache_key(spec))
            flats[(id(spec), idx)] = np.asarray(res["flat"])

    sched_lib.run_cohorts([co for _, co in workload], sink=sink,
                          jobs=jobs, do_eval=False)
    return flats


def async_rows(rounds: int = 400, jobs: int = 2, reps: int = 3):
    """Serial vs async wall clock on two workloads, bit-exactness counted.

    Methodology notes, both load-bearing on a small shared container:

      * paper-length ``rounds`` (default 400, not the merge section's 40)
        keep per-cohort EXECUTION non-trivial — at CI-quick rounds the
        fig grids are pure compile and the comparison times XLA:CPU's
        internally serialized compiler, not the runtime's overlap;
      * each layout runs ``reps`` times and the committed walls are
        MEDIANS: single compile walls vary ~30% run-to-run here, more
        than the overlap win itself.
    """
    fig_specs = list(_fig_specs(rounds).values())
    mlp_spec = SweepSpec(
        axes={"seed": (0, 1), "policy": ("inflota", "random")},
        base={"task": "mlp", "U": 10, "k_bar": 20,
              "rounds": max(rounds // 12, 20), "lr": 0.05,
              "backend": "jnp"}, eval=False)
    workloads = {
        "fig4_5_6": [(s, co) for s in fig_specs
                     for co in cohorts(cells(s))],
        "mlp": [(mlp_spec, co) for co in cohorts(cells(mlp_spec))],
    }
    rows = []
    for name, workload in workloads.items():
        n = sum(len(co) for _, co in workload)
        t_serial, t_async = [], []
        serial = asynced = None
        for _ in range(reps):
            jax.clear_caches()
            t0 = time.time()
            serial = _serial_cohorts(workload,
                                     SweepStore(tempfile.mkdtemp()))
            t_serial.append(time.time() - t0)
            jax.clear_caches()
            t0 = time.time()
            asynced = _async_cohorts(workload,
                                     SweepStore(tempfile.mkdtemp()), jobs)
            t_async.append(time.time() - t0)
        exact = sum(int(np.array_equal(serial[k], asynced[k]))
                    for k in serial)
        ts, ta = statistics.median(t_serial), statistics.median(t_async)
        rows += [
            {"name": f"async_{name}_serial",
             "metric": "cells/median_wall_s/runs_per_s",
             "value": [n, round(ts, 2), round(n / ts, 3)]},
            {"name": f"async_{name}_jobs{jobs}",
             "metric": "cells/median_wall_s/runs_per_s",
             "value": [n, round(ta, 2), round(n / ta, 3)]},
            {"name": f"async_{name}_speedup", "metric": "serial/async",
             "value": round(ts / ta, 2)},
            {"name": f"async_{name}_bitexact", "metric": f"cells=={n}",
             "value": exact},
        ]
    return rows


def trace_overhead_rows(rounds: int = 400, reps: int = 3):
    """Lifecycle tracing on vs off over the fig4_5_6 grids (ISSUE-8).

    Tracing must be close to free (<3% target) AND a pure observer.
    Methodology: one untimed warm-up run pays every compile, then
    ``reps`` alternating untraced/traced runs against fresh stores with
    a warm jit cache — the steady-state walls are what tracing can
    actually tax.  Also counts traced-vs-untraced byte-identical cells
    (result files only; the trace itself lives under ``meta/``).
    """
    import os

    from repro.obs import trace as trace_lib

    specs = list(_fig_specs(rounds).values())
    n = sum(len(cells(s)) for s in specs)

    def one_run(traced: bool) -> tuple[float, str]:
        root = tempfile.mkdtemp()
        if traced:
            trace_lib.install(trace_lib.trace_dir_for(root))
        try:
            t0 = time.time()
            for spec in specs:
                run_spec(spec, store=SweepStore(root), verbose=False)
            return time.time() - t0, root
        finally:
            trace_lib.uninstall()

    one_run(False)                       # warm-up: compiles paid here
    t_off, t_on = [], []
    root_off = root_on = None
    for _ in range(reps):
        w, root_off = one_run(False)
        t_off.append(w)
        w, root_on = one_run(True)
        t_on.append(w)

    def cell_bytes(root):
        return {f: open(os.path.join(root, f), "rb").read()
                for f in sorted(os.listdir(root)) if f.endswith(".json")}

    off_files, on_files = cell_bytes(root_off), cell_bytes(root_on)
    # the fig grids overlap at the all-defaults cell, so unique store
    # files < cells; compare files (the byte-identity unit), not cells
    exact = sum(int(off_files[f] == on_files.get(f)) for f in off_files)
    toff, ton = statistics.median(t_off), statistics.median(t_on)
    pct = 100.0 * (ton - toff) / toff
    return [
        {"name": "trace_overhead_fig4_5_6_off",
         "metric": "cells/median_wall_s",
         "value": [n, round(toff, 2)]},
        {"name": "trace_overhead_fig4_5_6_on",
         "metric": "cells/median_wall_s",
         "value": [n, round(ton, 2)]},
        {"name": "trace_overhead_fig4_5_6_pct", "metric": "percent",
         "value": round(pct, 2)},
        {"name": "trace_overhead_bitexact",
         "metric": f"files=={len(off_files)}",
         "value": exact},
    ]


def flight_overhead_rows(rounds: int = 400, reps: int = 3,
                         every: int = 50):
    """Flight taps on vs off over the fig4_5_6 grids (ISSUE-10).

    BOTH arms run blocked (``checkpoint_every=every``) so the measured
    delta is the tap itself — the io_callback per block plus the
    host-side ring/sentinel/status work — not blocked-vs-whole-scan
    execution.  Same methodology as :func:`trace_overhead_rows`: one
    untimed warm-up pays the compiles, then ``reps`` alternating
    untapped/tapped runs against fresh stores; committed walls are
    medians, and tapped-vs-untapped store files must stay byte-identical
    (the flight record lives under ``meta/``).
    """
    import os

    from repro.obs import flight as flight_lib

    specs = list(_fig_specs(rounds).values())
    n = sum(len(cells(s)) for s in specs)

    def one_run(tapped: bool) -> tuple[float, str]:
        root = tempfile.mkdtemp()
        if tapped:
            flight_lib.install(flight_lib.flight_dir_for(root))
        try:
            t0 = time.time()
            for spec in specs:
                run_spec(spec, store=SweepStore(root),
                         checkpoint_every=every, verbose=False)
            return time.time() - t0, root
        finally:
            flight_lib.uninstall()

    one_run(False)                       # warm-up: compiles paid here
    t_off, t_on = [], []
    root_off = root_on = None
    for _ in range(reps):
        w, root_off = one_run(False)
        t_off.append(w)
        w, root_on = one_run(True)
        t_on.append(w)

    def cell_bytes(root):
        return {f: open(os.path.join(root, f), "rb").read()
                for f in sorted(os.listdir(root)) if f.endswith(".json")}

    off_files, on_files = cell_bytes(root_off), cell_bytes(root_on)
    exact = sum(int(off_files[f] == on_files.get(f)) for f in off_files)
    toff, ton = statistics.median(t_off), statistics.median(t_on)
    pct = 100.0 * (ton - toff) / toff
    return [
        {"name": "flight_overhead_fig4_5_6_off",
         "metric": "cells/median_wall_s",
         "value": [n, round(toff, 2)]},
        {"name": "flight_overhead_fig4_5_6_on",
         "metric": "cells/median_wall_s",
         "value": [n, round(ton, 2)]},
        {"name": "flight_overhead_fig4_5_6_pct", "metric": "percent",
         "value": round(pct, 2)},
        {"name": "flight_overhead_bitexact",
         "metric": f"files=={len(off_files)}",
         "value": exact},
    ]


def run(rounds: int = 60, json_path: str | None = None,
        merge_rounds: int = 40, async_rounds: int | None = None,
        async_reps: int = 3):
    # the serial-vs-async comparison runs FIRST, in a cold process, so
    # both layouts pay identical cold-start costs; the other sections
    # then reuse the warm process (their comparisons are internal)
    arows = async_rows(rounds=merge_rounds * 10 if async_rounds is None
                       else async_rounds, reps=async_reps)

    spec = _spec(rounds)
    n = len(cells(spec))

    t0 = time.time()
    seq_flats = _sequential(rounds)
    t_seq = time.time() - t0

    t0 = time.time()
    results = run_spec(spec)
    jax.block_until_ready([r["flat"] for r in results])
    t_vec = time.time() - t0

    exact = sum(int(np.array_equal(a, r["flat"]))
                for a, r in zip(seq_flats, results))
    seq_rps, vec_rps = n / t_seq, n / t_vec
    rows = [
        {"name": f"sweep_seq_runs_per_s_n{n}", "metric": "runs/s",
         "value": round(seq_rps, 3)},
        {"name": f"sweep_vec_runs_per_s_n{n}", "metric": "runs/s",
         "value": round(vec_rps, 3)},
        {"name": "sweep_speedup", "metric": "vec/seq",
         "value": round(vec_rps / seq_rps, 2)},
        {"name": "sweep_bitexact", "metric": f"cells=={n}",
         "value": exact},
    ]
    rows += cohort_merge_rows(rounds=merge_rounds)
    rows += arows
    rows += trace_overhead_rows(rounds=merge_rounds * 10
                                if async_rounds is None else async_rounds,
                                reps=async_reps)
    rows += flight_overhead_rows(rounds=merge_rounds * 10
                                 if async_rounds is None
                                 else async_rounds,
                                 reps=async_reps)
    if json_path:
        doc = {"host": platform.node(), "device": common.device_info(),
               "grid": {"seeds": SEEDS, "policies": list(POLICIES),
                        "channels": [c or "exp_iid" for c in CHANNELS],
                        "rounds": rounds, "U": U, "k_bar": K_BAR,
                        "merge_rounds": merge_rounds},
               "rows": rows}
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--merge-rounds", type=int, default=40)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    for r in run(rounds=args.rounds, json_path=args.json,
                 merge_rounds=args.merge_rounds):
        print(f"{r['name']},{r['metric']},{r['value']}")
