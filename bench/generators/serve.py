"""Generator of shared-daemon traffic: a small lab's clients on one sweep
daemon (``SweepService`` behind ``make_server``, ``python -m repro.serve``'s
defaults, a fresh store per run), in the benchmark's own process.

Traffic parameters (``bench/traffic/<mix>.json``):

  clients            client threads, each a closed loop without think time
  poll_s             how often a client polls its request
  policies           one policy per request, alternating per client
  seeds_per_request  the request's seed block
  rounds, data_seed  as in the grid traffic
  repeat_every       every n-th request of a client repeats the latest
                     grid another client sent (a store hit, or shared
                     while in flight); the others are new seed blocks

Seed blocks come from ``--seed``.  End-to-end metric: ``serve_cells_per_s``
(cells delivered for the requests issued in the window, over the time
from the window's start to the last of them).  In this closed loop the
mean request latency is the clients over the request rate, so it adds
nothing to the rate; a latency tail needs more requests than this mix
sends in a window.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

SEED_MAX = 2 ** 31 - 1


def _spec(ctx, policy, seeds):
    from bench.generators import grid
    return grid.spec(ctx, (policy,), seeds)


def setup(ctx):
    from repro.serve import api as api_lib
    from repro.serve import client as client_lib
    from repro.serve import session as session_lib

    t = ctx.traffic
    svc = session_lib.SweepService(os.path.join(ctx.scratch, "store"))
    server = api_lib.make_server(svc, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever,
                              kwargs={"poll_interval": 0.2}, daemon=True)
    thread.start()
    addr = "%s:%d" % server.server_address
    state = {"svc": svc, "server": server, "thread": thread, "addr": addr,
             "rng": np.random.default_rng(ctx.seed),
             "lock": threading.Lock()}
    # warm-up: one request per policy at the window's shapes, on seeds
    # the window never sends
    for policy in t["policies"]:
        seeds = state["rng"].integers(0, SEED_MAX, t["seeds_per_request"])
        client_lib.submit_and_wait(addr, _spec(ctx, policy, seeds),
                                   client="warmup", poll_s=t["poll_s"],
                                   timeout_s=600)
    return state


def _client(ctx, state, i, t_end, sent, log):
    import jax
    from repro.serve import client as client_lib
    t = ctx.traffic
    n_new = 0
    j = 0
    while True:
        with state["lock"]:
            others = [g for g in sent if g[0] != i]
            if j % t["repeat_every"] == t["repeat_every"] - 1 and others:
                _, policy, seeds = others[-1]
                kind = "repeat"
            else:
                policy = t["policies"][(i + n_new) % len(t["policies"])]
                seeds = tuple(int(s) for s in state["rng"].integers(
                    0, SEED_MAX, t["seeds_per_request"]))
                n_new += 1
                kind = "new"
            sent.append((i, policy, seeds))
        t0 = time.time()
        try:
            with jax.profiler.TraceAnnotation("bench.serve.request"):
                results, snap = client_lib.submit_and_wait(
                    state["addr"], _spec(ctx, policy, seeds),
                    client=f"client{i}", poll_s=t["poll_s"], timeout_s=300)
            error = None
        except Exception as e:             # counted as a failed request
            results, snap, error = [], {}, f"{type(e).__name__}: {e}"
        log.append({"client": i, "kind": kind, "policy": policy,
                    "seeds": seeds, "t0": t0, "t1": time.time(),
                    "results": results, "counts": snap.get("counts"),
                    "error": error})
        j += 1
        if time.time() >= t_end:
            break


def window(ctx, state, seconds):
    from repro.obs import trace as obs_trace
    svc = state["svc"]
    if ctx.trace:
        obs_trace.install(os.path.join(ctx.scratch, "obs_trace"))
    before = dict(svc.stats()["cells"])
    sent, log = [], []
    t0 = time.time()
    threads = [threading.Thread(target=_client,
                                args=(ctx, state, i, t0 + seconds, sent,
                                      log), daemon=True)
               for i in range(ctx.traffic["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=seconds + 600)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a client did not finish within 600 s of the "
                           "window's close")
    t_last = max(r["t1"] for r in log)
    after = dict(svc.stats()["cells"])
    ctx.window.update(
        elapsed_s=t_last - t0, requests=len(log),
        cells={k: after.get(k, 0) - before.get(k, 0) for k in after})
    if ctx.trace:
        obs_trace.uninstall()          # closes and flushes the recorder
        ctx.window["obs_spans"] = _read_spans(
            os.path.join(ctx.scratch, "obs_trace"))
    return log


def _read_spans(trace_dir):
    import json
    spans = []
    for name in sorted(os.listdir(trace_dir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(trace_dir, name)) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("ph") == "X":
                    spans.append((rec["name"], rec["dur"] / 1e3))
    return spans


def end_to_end(ctx, state, log):
    cells = sum(len(r["results"]) for r in log if r["error"] is None)
    return {"serve_cells_per_s": cells / ctx.window["elapsed_s"]}


def attempted_failed(ctx, log):
    bad = sum(1 for r in log if r["error"] is not None
              or any(doc is None for doc in r["results"])
              or len(r["results"]) != ctx.traffic["seeds_per_request"])
    return len(log), bad


def release(ctx, state):
    state["server"].shutdown()
    state["server"].server_close()
    state["svc"].close()
    state["thread"].join(timeout=10)
    state.clear()


def _reference(ctx, log, precision):
    """{policy: {seed: {series: (rounds,)}}} of every cell in the log,
    run in blocks of the request's size."""
    from bench.reference import mlp_fl
    t = ctx.traffic
    block = t["seeds_per_request"]
    ref = mlp_fl.Reference(ctx.config, t["data_seed"], precision)
    refs = {}
    for policy in t["policies"]:
        seeds = sorted({s for r in log if r["policy"] == policy
                        for s in r["seeds"]})
        refs[policy] = {}
        for k in range(0, len(seeds), block):
            chunk = seeds[k:k + block]
            out = ref.run(policy, chunk + [chunk[-1]] * (block - len(chunk)),
                          t["rounds"])
            for e, s in enumerate(chunk):
                refs[policy][s] = {name: out[name][e]
                                   for name in mlp_fl.SERIES}
    return refs


def control_answers(ctx, state, log):
    """Every delivered cell as the reference computes it in bfloat16: the
    control, which ``check`` must refuse."""
    refs = _reference(ctx, log, "bf16")
    out = []
    for r in log:
        docs = [None if d is None else
                {**d, "history": refs[r["policy"]][int(d["cell"]["seed"])]}
                for d in r["results"]]
        out.append({**r, "results": docs})
    return out


def check(ctx, log):
    """Every delivered cell (computed, shared or hit) against the plain
    reference: the per-round history over the first rounds (``early``)
    and the whole history, per policy, summed up over the cells as the
    configuration's limits name it (``_max`` the worst cell, ``_median``
    the median cell), on each path apart (``_PATHS``), the worse path's
    number compared."""
    from bench import compare
    from bench.reference import mlp_fl
    c = ctx.config
    refs = _reference(ctx, log, "highest")
    gaps = {}
    for r in log:
        if r["error"] is not None:
            continue
        path = gaps.setdefault(_PATHS[r["kind"]], {})
        for doc in r["results"]:
            if doc is None:
                continue
            early, whole = compare.history_gaps(
                doc["history"], refs[r["policy"]][int(doc["cell"]["seed"])],
                mlp_fl.SERIES, c["early_rounds"])
            path.setdefault(("early", r["policy"]), []).append(early)
            path.setdefault(("history", r["policy"]), []).append(whole)
    return worse_path(gaps, c["limits"])


# a new request's cells are computed; a repeat's are store hits, or shared
# with the cohort in flight
_PATHS = {"new": "computed", "repeat": "hit_or_shared"}


def worse_path(gaps_by_path: dict, limits: dict) -> dict:
    """Each number compared, summed up over one path's cells at a time,
    and the worse path's reading kept: a fault confined to the store-hit
    and shared path moves that path's median cell."""
    from bench import compare
    out = {}
    for gaps in gaps_by_path.values():
        for name, c in compare.summarize(gaps, limits).items():
            if name not in out or c["value"] > out[name]["value"]:
                out[name] = c
    return out
