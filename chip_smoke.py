#!/usr/bin/env python3
"""Smoke test of the OTA-FL system on a TPU: round, sweep cohorts, daemon.

    python chip_smoke.py              # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4    # the device-count comparisons only

One process drives every phase.  With no arguments:

  (a) ``FLTrainer`` on the paper's Sec. VI-B MLP (D = 50,890, U = 20),
      5 rounds as one scan, Pallas backend against jnp;
  (b) a vmapped sweep cohort (``run_spec``, seed x policy) on the Pallas
      backend, written to a store, against the same grid on jnp;
  (c) a worker-sharded round at U = 10^6 with 1,000 shard blocks
      (``linreg``), Pallas against jnp;
  (d) the sweep daemon (``SweepService`` behind ``make_server``) served
      (b)'s grid over HTTP twice: the second submission must be all
      store hits with no new dispatch.

With ``--chips 4``: (b)'s grid with its experiment axis sharded over four
devices against one device, and a worker-mesh round against logical
mode at U = 10^5, S = 100.

Each phase prints one JSON line: wall and backend-compile seconds, the
largest relative difference of its comparison and its tolerance, and
whether the compiled Pallas program holds a Mosaic kernel
(``tpu_custom_call``).  A phase fails on an exception, a non-finite
output, a missing or quarantined cell, or a difference over tolerance.
The last line, printed only when every phase passed, is
``{"ok": true, "device": {...}}``.  Without a TPU, or outside a
checkout, the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

TOL = 1e-4          # max relative difference of every comparison
MLP = {"task": "mlp", "U": 20, "k_bar": 40, "lr": 0.1,
       "case": "gd_nonconvex", "k_b": 16}        # paper Sec. VI-B
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class PhaseError(AssertionError):
    pass


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and
    cache hits, from JAX's monitoring events, across all threads."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1


def rel_diff(a, b) -> float:
    """max |a - b| / max |b| over the paired leaves of two pytrees."""
    import jax
    import numpy as np
    worst = 0.0
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        raise PhaseError(f"{len(la)} leaves against {len(lb)}")
    for x, y in zip(la, lb):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        if x.shape != y.shape:
            raise PhaseError(f"shape {x.shape} against {y.shape}")
        err = float(np.max(np.abs(x - y), initial=0.0))
        if err:
            worst = max(worst, err / max(float(np.max(np.abs(y))), 1e-30))
    return worst


def check_finite(tree, what: str) -> None:
    import jax
    import numpy as np
    for leaf in jax.tree.leaves(tree):
        if not np.all(np.isfinite(np.asarray(leaf, np.float64))):
            raise PhaseError(f"{what} holds a non-finite value")


def kernel_compiled(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _numeric(history):
    return {k: v for k, v in history.items()
            if k not in ("round", "params", "compile_s")}


# ------------------------------------------------------------------ phases

def phase_trainer(U: int = 20, rounds: int = 5):
    """(a) FLTrainer, MLP, scan=True: Pallas against jnp."""
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core.channel import ChannelConfig
    from repro.core.convergence import LearningConstants
    from repro.core.objectives import Case
    from repro.data.tasks import build_task_data
    from repro.fl.trainer import FLConfig, FLTrainer

    task, workers, test = build_task_data(
        "mlp", U=U, k_bar=MLP["k_bar"], data_seed=0)
    key = jax.random.PRNGKey(0)
    hist, trainers = {}, {}
    for backend in ("pallas", "jnp"):
        cfg = FLConfig(rounds=rounds, lr=MLP["lr"], policy="inflota",
                       case=Case(MLP["case"]), k_b=MLP["k_b"],
                       channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                       constants=LearningConstants(sigma2=1e-4),
                       backend=backend, scan=True, seed=0)
        trainers[backend] = FLTrainer(task, workers, cfg)
        hist[backend] = trainers[backend].run(key=key, eval_data=test)
        check_finite(hist[backend]["params"], f"{backend} params")
        check_finite(_numeric(hist[backend]), f"{backend} metrics")
    params_diff = rel_diff(hist["pallas"]["params"], hist["jnp"]["params"])
    metric_diffs = {k: rel_diff(v, hist["jnp"][k])
                    for k, v in _numeric(hist["pallas"]).items()}
    return {
        "max_rel_diff": max(params_diff, *metric_diffs.values()),
        "params_rel_diff": params_diff, "metric_rel_diffs": metric_diffs,
        "tpu_custom_call": kernel_compiled(trainers["pallas"].compiled),
        "D": int(ravel_pytree(hist["pallas"]["params"])[0].size),
        "U": U, "rounds": rounds,
        "final_ce": hist["pallas"]["ce"][-1],
    }


def sweep_spec(backend: str, rounds: int = 5, U: int = 20):
    from repro.sweep import SweepSpec
    return SweepSpec(axes={"seed": (0, 1), "policy": ("inflota", "random")},
                     base={**MLP, "U": U, "rounds": rounds,
                           "backend": backend})


def _check_results(results, what: str) -> None:
    missing = [i for i, r in enumerate(results) if r is None]
    if missing:
        raise PhaseError(f"{what}: cells {missing} missing or quarantined")
    for r in results:
        check_finite(r["metrics"], f"{what} metrics")
        check_finite(r["history"], f"{what} history")
        if "flat" in r:
            check_finite(r["flat"], f"{what} params")


def _results_diffs(a, b):
    """Largest relative differences of two grids' cells: metrics,
    histories, and final params where both hold them (store documents
    do not)."""
    worst = {"metrics": 0.0}
    for x, y in zip(a, b):
        parts = {"metrics": (x["metrics"], y["metrics"])}
        parts.update((f"history.{k}", (v, y["history"][k]))
                     for k, v in x["history"].items())
        if "flat" in x and "flat" in y:
            parts["flat"] = (x["flat"], y["flat"])
        for part, (u, v) in parts.items():
            worst[part] = max(worst.get(part, 0.0), rel_diff(u, v))
    return worst


def store_files(root: str):
    """{relative path: bytes} of a store's cell documents (not meta/)."""
    out = {}
    for dirpath, _, names in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        if rel.split(os.sep)[0] in ("meta", ".runtime"):
            continue
        for n in names:
            with open(os.path.join(dirpath, n), "rb") as f:
                out[os.path.normpath(os.path.join(rel, n))] = f.read()
    return out


def phase_sweep(tmp: str, rounds: int = 5, U: int = 20):
    """(b) run_spec: a vmapped Pallas cohort with batched scalars, to a
    store, against the same grid on jnp."""
    import jax
    from repro.sweep import SweepStore, cells, cohorts, run_spec
    from repro.sweep.grid import prepare_cohort

    spec = sweep_spec("pallas", rounds, U)
    store = os.path.join(tmp, "sweep_pallas")
    res_p = run_spec(spec, store=SweepStore(store))
    res_j = run_spec(sweep_spec("jnp", rounds, U))
    _check_results(res_p, "pallas grid")
    _check_results(res_j, "jnp grid")
    n_files = len(store_files(store))
    if n_files != len(res_p):
        raise PhaseError(f"store holds {n_files} cells, grid has "
                         f"{len(res_p)}")
    cohort = next(c for c in cohorts(cells(spec))
                  if c.static["policy"] == "inflota")
    prep = prepare_cohort(cohort, do_eval=spec.eval)
    compiled = jax.jit(jax.vmap(prep.run_one)).lower(prep.batch).compile()
    diffs = _results_diffs(res_p, res_j)
    return {"max_rel_diff": max(diffs.values()), "rel_diffs": diffs,
            "tpu_custom_call": kernel_compiled(compiled),
            "cells": len(res_p), "cohort_experiments": len(cohort),
            "store_cells": n_files}, (spec, store, res_p)


def worker_arrays(U: int, K: int = 2, seed: int = 0):
    """(X, Y, mask, k_i) of U equal linreg workers (the ``synthetic``
    generator family), built as arrays rather than 10^6 worker tuples."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, 1.0, size=(U, K)).astype(np.float32)
    y = (-2.0 * x + 1.0 + 0.4 * rng.normal(size=(U, K))).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(y),
            jnp.ones((U, K), jnp.float32), jnp.full((U,), K, jnp.float32))


def sharded_run(U: int, shards: int, rounds: int, backend: str,
                mesh=None):
    """A worker-sharded linreg run; returns (flat, stats, compiled)."""
    import jax
    import numpy as np
    from jax.flatten_util import ravel_pytree
    from repro.core.channel import ChannelConfig
    from repro.core.convergence import LearningConstants
    from repro.fl import worker_shard
    from repro.fl.engine import FLConfig
    from repro.fl.models import linreg_model

    task = linreg_model()
    X, Y, mask, k_i = worker_arrays(U)
    params0 = task.init(jax.random.PRNGKey(7))
    cfg = FLConfig(rounds=rounds, lr=0.05, policy="inflota",
                   worker_sharding=shards,
                   channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                   constants=LearningConstants(sigma2=1e-4),
                   backend=backend)
    eng = worker_shard.build_sharded_engine(task, X, Y, mask, k_i, cfg,
                                            params0, mesh=mesh)
    flat0, _ = ravel_pytree(params0)
    st = eng.init(flat0, jax.random.PRNGKey(0))
    compiled = jax.jit(eng.step).lower(st).compile()
    stats = []
    for _ in range(rounds):
        st, s = compiled(st)
        stats.append(s)
    stats = jax.tree.map(lambda *xs: np.stack(xs), *jax.device_get(stats))
    flat = np.asarray(st.flat)
    check_finite(flat, f"{backend} params")
    check_finite(stats, f"{backend} round stats")
    return flat, stats, compiled


def phase_worker_sharded(U: int = 10**6, shards: int = 1000,
                         rounds: int = 2):
    """(c) worker-sharded rounds, U_b = U / shards: Pallas against jnp."""
    f_p, s_p, compiled = sharded_run(U, shards, rounds, "pallas")
    f_j, s_j, _ = sharded_run(U, shards, rounds, "jnp")
    return {"max_rel_diff": max(rel_diff(f_p, f_j),
                                rel_diff(s_p._asdict(), s_j._asdict())),
            "tpu_custom_call": kernel_compiled(compiled),
            "U": U, "shards": shards, "U_b": U // shards,
            "snr_final": float(s_p.snr[-1])}


def phase_daemon(tmp: str, spec, ref_store: str, ref_results):
    """(d) SweepService + make_server: (b)'s grid twice over HTTP."""
    from repro.serve import api as api_lib
    from repro.serve import client as client_lib
    from repro.serve import session as session_lib

    root = os.path.join(tmp, "daemon")
    svc = session_lib.SweepService(root, jobs=2, max_retries=0, poll_s=0.1)
    server = api_lib.make_server(svc, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever,
                              kwargs={"poll_interval": 0.1}, daemon=True)
    thread.start()
    addr = "%s:%d" % server.server_address
    try:
        first, snap1 = client_lib.submit_and_wait(addr, spec, poll_s=0.1,
                                                  timeout_s=900)
        _check_results(first, "first submission")
        if snap1["counts"] != {"done": len(first)}:
            raise PhaseError(f"first submission settled {snap1['counts']}")
        dispatched = svc.engine.counters.get("cohorts_dispatched")
        second, snap2 = client_lib.submit_and_wait(addr, spec, poll_s=0.1,
                                                   timeout_s=60)
        _check_results(second, "second submission")
        if snap2["counts"] != {"hit": len(second)}:
            raise PhaseError(f"resubmission was not all hits: "
                             f"{snap2['counts']}")
        if svc.engine.counters.get("cohorts_dispatched") != dispatched:
            raise PhaseError("resubmission dispatched a cohort")
        stats = svc.stats()
        bad = {k: v for k, v in stats["cohorts"].items() if k != "done"}
        if bad or svc.engine.counters.get("cohorts_quarantined"):
            raise PhaseError(f"cohorts not done: {stats['cohorts']}")
        diffs = _results_diffs(first, ref_results)
        return {"max_rel_diff": max(*diffs.values(), *_results_diffs(
                    second, first).values()),
                "rel_diffs_to_b": diffs,
                "tpu_custom_call": "same cohort programs as phase b",
                "cells": len(first), "hits_on_resubmit": len(second),
                "cohorts_dispatched": dispatched,
                "store_identical_to_b":
                    store_files(root) == store_files(ref_store)}
    finally:
        server.shutdown()
        server.server_close()
        svc.close()
        thread.join(timeout=10)


def phase_sweep_devices(tmp: str, n: int = 4, rounds: int = 5,
                        U: int = 20):
    """(b)'s grid, experiment axis over ``n`` devices against one."""
    from repro.sweep import SweepStore, run_spec
    from repro.sweep import shard as shard_lib

    spec = sweep_spec("pallas", rounds, U)
    one, many = os.path.join(tmp, "one"), os.path.join(tmp, "many")
    res_1 = run_spec(spec, store=SweepStore(one))
    mesh = shard_lib.sweep_mesh(n)
    if shard_lib.shard_count(mesh) != n:
        raise PhaseError(f"sweep mesh spans {shard_lib.shard_count(mesh)} "
                         f"devices, wanted {n}")
    res_n = run_spec(spec, store=SweepStore(many), mesh=mesh)
    _check_results(res_1, "1-device grid")
    _check_results(res_n, f"{n}-device grid")
    diffs = _results_diffs(res_n, res_1)
    return {"max_rel_diff": max(diffs.values()), "rel_diffs": diffs,
            "stores_byte_identical": store_files(one) == store_files(many),
            "devices": n}


def phase_worker_mesh(n: int = 4, U: int = 10**5, shards: int = 100,
                      rounds: int = 2):
    """worker_mesh (shard_map over ``n`` devices) against logical mode."""
    import numpy as np
    from repro.fl import worker_shard

    mesh = worker_shard.worker_mesh(n)
    out = {"U": U, "shards": shards, "devices": n}
    worst = 0.0
    for backend in ("pallas", "jnp"):
        f_l, s_l, _ = sharded_run(U, shards, rounds, backend)
        f_m, s_m, compiled = sharded_run(U, shards, rounds, backend,
                                         mesh=mesh)
        worst = max(worst, rel_diff(f_m, f_l),
                    rel_diff(s_m._asdict(), s_l._asdict()))
        out[f"{backend}_byte_identical"] = bool(
            np.array_equal(f_m, f_l) and all(
                np.array_equal(a, b) for a, b in zip(s_m, s_l)))
        if backend == "pallas":
            out["tpu_custom_call"] = kernel_compiled(compiled)
    return {"max_rel_diff": worst, **out}


# -------------------------------------------------------------------- main

def run_phase(name: str, fn, clock) -> bool:
    t0 = time.perf_counter()
    c0, h0 = clock.seconds, clock.hits
    line = {"phase": name}
    try:
        line.update(fn())
        passed = line["max_rel_diff"] <= TOL and \
            line.get("tpu_custom_call") is not False
        if not passed:
            line["error"] = "over tolerance or no Mosaic kernel"
    except Exception as e:                       # reported, then fails
        traceback.print_exc()
        line["error"] = f"{type(e).__name__}: {e}"
        passed = False
    line.update(passed=passed, tol=TOL,
                wall_s=time.perf_counter() - t0,
                compile_s=clock.seconds - c0,
                compile_cache_hits=clock.hits - h0)
    print(json.dumps(line, default=str), flush=True)
    return passed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the comparisons across four devices")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (first device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.runtime import compile_cache
    print(json.dumps({"compile_cache": compile_cache.enable(),
                      "device_kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)
    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phases = [
                ("sweep_4dev_vs_1dev",
                 lambda: phase_sweep_devices(os.path.join(tmp, "e"))),
                ("worker_mesh_vs_logical", phase_worker_mesh),
            ]
        else:
            state = {}

            def sweep():
                line, state["b"] = phase_sweep(os.path.join(tmp, "b"))
                return line

            def daemon():
                if "b" not in state:
                    raise PhaseError("phase b produced no grid to serve")
                return phase_daemon(tmp, *state["b"])

            phases = [("a_trainer_mlp", phase_trainer),
                      ("b_sweep_cohort_mlp", sweep),
                      ("c_worker_sharded_1e6", phase_worker_sharded),
                      ("d_daemon", daemon)]
        ok = all([run_phase(name, fn, clock) for name, fn in phases])
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
