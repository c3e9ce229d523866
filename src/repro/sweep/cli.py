"""``python -m repro.sweep`` — run experiment grids from the command line.

Examples:

    # 8 seeds x 2 policies x 2 channels, one vectorized computation per
    # cohort, results cached under sweeps/store, tidy CSV on stdout
    python -m repro.sweep --task linreg --rounds 100 \
        --axis seed=0:8 --axis policy=inflota,random \
        --axis channel=exp_iid,gauss_markov --store sweeps/store

    # grid from a JSON spec file
    python -m repro.sweep --spec myspec.json --csv out.csv

    # async runtime: dispatch cohorts from 2 threads, up to 4 in flight,
    # store writes on a background writer thread (same results as serial)
    python -m repro.sweep --spec myspec.json --store sweeps/store --jobs 2

    # multi-host: one process per host against a shared store root;
    # hosts work-steal cohorts, host 0 collects (see docs/runtime.md)
    python -m repro.sweep --spec myspec.json --store /shared/store \
        --coordinator head:8476 --num-hosts 4 --host-id $K --jobs 2

    # fault tolerance: checkpoint the scan carry every 50 rounds,
    # retry flaky cohorts twice, quarantine persistent failures; after
    # a crash, --resume picks up from the last checkpoint
    python -m repro.sweep --spec myspec.json --store sweeps/store \
        --checkpoint-every 50 --max-retries 2 --quarantine
    python -m repro.sweep --spec myspec.json --store sweeps/store \
        --checkpoint-every 50 --resume

    # client mode: post the same grid to a running sweep service
    # daemon (python -m repro.serve) and poll to completion — cached
    # cells come back instantly, output is identical to a local run
    python -m repro.sweep --submit 127.0.0.1:8477 --task linreg \
        --rounds 10 --axis seed=0:8 --csv out.csv

Spec JSON mirrors ``SweepSpec``: {"axes": {...}, "base": {...},
"eval": true, "tail": 10}.  Axis values on the command line are comma
lists (``policy=inflota,random``) or integer ranges (``seed=0:8``);
values parse as int, then float, then string (``none`` -> null).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, List, Tuple

from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.runtime import compile_cache
from repro.sweep import shard as shard_lib
from repro.sweep import store as store_lib
from repro.sweep.grid import DEFAULTS, SweepSpec, cells, cohorts, run_spec


def _parse_jobs(s: str) -> Any:
    if s.strip().lower() == "auto":
        return "auto"
    try:
        return int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--jobs wants an integer or 'auto', got {s!r}") from None


def parse_value(s: str) -> Any:
    low = s.strip().lower()
    if low in ("none", "null"):
        return None
    if low in ("true", "false"):
        return low == "true"
    for cast in (int, float):
        try:
            return cast(s)
        except ValueError:
            pass
    return s.strip()


def parse_axis(arg: str) -> Tuple[str, List[Any]]:
    """``name=v1,v2`` or ``name=start:stop[:step]`` (int range)."""
    if "=" not in arg:
        raise ValueError(f"--axis wants NAME=VALUES, got {arg!r}")
    name, _, rhs = arg.partition("=")
    name = name.strip()
    if ":" in rhs:
        parts = [int(p) for p in rhs.split(":")]
        if len(parts) == 2:
            values: List[Any] = list(range(parts[0], parts[1]))
        elif len(parts) == 3:
            values = list(range(parts[0], parts[1], parts[2]))
        else:
            raise ValueError(f"bad range {rhs!r} for axis {name!r}")
    else:
        values = [parse_value(v) for v in rhs.split(",") if v.strip() != ""]
    if not values:
        raise ValueError(f"axis {name!r} has no values")
    return name, values


def build_spec(args) -> SweepSpec:
    """A --spec file provides the starting point; every other flag given
    on the command line overrides it (axes by name, base field-wise)."""
    axes: dict = {}
    base: dict = {}
    do_eval, tail = True, 10
    if args.spec:
        with open(args.spec) as f:
            doc = json.load(f)
        axes = {k: list(v) for k, v in doc["axes"].items()}
        base = dict(doc.get("base", {}))
        do_eval = doc.get("eval", True)
        tail = doc.get("tail", 10)
    for a in args.axis:
        name, values = parse_axis(a)
        axes[name] = values
    for field in ("task", "U", "k_bar", "data_seed", "rounds", "lr",
                  "sigma2", "p_max", "eps", "rho", "L", "policy",
                  "channel", "case", "k_b", "backend", "eval_every",
                  "seed", "U_shards"):
        v = getattr(args, field)
        if v is not None:
            base[field] = v
    if args.no_eval:
        do_eval = False
    if args.tail is not None:
        tail = args.tail
    return SweepSpec(axes=axes, base=base, eval=do_eval, tail=tail)


def format_schedule(plan, jobs: int, dispatch_ahead,
                    num_hosts: int = 1) -> List[str]:
    """The async runtime's view of the plan: dispatch order by cost
    estimate, the in-flight window, and (multi-host) which host runs
    which cohorts — printed by ``--dry-run`` so a user can predict a
    concurrent run before paying for it."""
    from repro.runtime import multihost as mh
    from repro.runtime import scheduler as sched_lib

    ahead = sched_lib.DEFAULT_DISPATCH_AHEAD if dispatch_ahead is None \
        else dispatch_ahead
    lines = [f"# schedule: jobs={jobs}, in-flight window={jobs + ahead} "
             f"(dispatch-ahead {ahead})"]
    order = " ".join(f"{e.order}(cost={e.cost})"
                     for e in sched_lib.schedule(plan))
    lines.append(f"#   dispatch order: {order}")
    if num_hosts > 1:
        for h, ids in enumerate(mh.partition(plan, num_hosts)):
            lines.append(f"#   host {h}: cohorts {_ranges(ids) or '(none)'}")
    return lines


def format_plan(cell_list, plan) -> List[str]:
    """Human-readable cohort partition: which cells share one compile.

    One block per cohort: the static fields that pin it (non-defaults
    only), the axes that vectorize INSIDE it (traced scalar operands and
    ragged data axes), and the grid indices of its member cells — so a
    user can see exactly why the grid compiles ``len(plan)`` times.
    """
    from repro.sweep.grid import _SCALARS, DATA_AXES   # internal layout

    lines = [f"# plan: {len(cell_list)} cells -> {len(plan)} cohort(s), "
             f"one compile each"]
    for n, co in enumerate(plan):
        pins = {k: v for k, v in co.static.items() if DEFAULTS.get(k) != v}
        # ragged-mergeable cohorts drop DATA_AXES from the static key;
        # uniform non-default values still pin the fleet — show them
        for name in DATA_AXES:
            if name not in co.static:
                vals = {c[name] for c in co.cells}
                if len(vals) == 1 and DEFAULTS.get(name) not in vals:
                    pins[name] = next(iter(vals))
        static = " ".join(f"{k}={v}" for k, v in sorted(pins.items())) \
            or "(all defaults)"
        vec = []
        for name in _SCALARS + ("seed",):
            vals = {c[name] for c in co.cells}
            if len(vals) > 1:
                vec.append(f"{name}x{len(vals)}")
        for name in DATA_AXES:
            vals = {c[name] for c in co.cells}
            if len(vals) > 1:
                vec.append(f"{name}x{len(vals)}(ragged)")
        tag = " ragged" if co.ragged else ""
        lines.append(f"# cohort {n} x{len(co)}{tag}: {static}")
        if vec:
            lines.append(f"#   vectorized: {' '.join(vec)}")
        lines.append(f"#   cells: {_ranges(co.indices)}")
    return lines


def _ranges(idx: List[int]) -> str:
    """Compact '0-3,7,9-11' rendering of sorted cell indices."""
    out, i = [], 0
    s = sorted(idx)
    while i < len(s):
        j = i
        while j + 1 < len(s) and s[j + 1] == s[j] + 1:
            j += 1
        out.append(str(s[i]) if i == j else f"{s[i]}-{s[j]}")
        i = j + 1
    return ",".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.sweep",
        description="run a whole experiment grid as vectorized cohorts")
    ap.add_argument("--spec", default=None, help="JSON spec file")
    ap.add_argument("--axis", action="append", default=[],
                    metavar="NAME=VALUES",
                    help="grid axis (repeatable): comma list or int range "
                         "a:b[:step]")
    for field in ("task", "policy", "channel", "case", "backend"):
        ap.add_argument(f"--{field}", default=None)
    for field in ("U", "k_bar", "data_seed", "rounds", "k_b",
                  "eval_every", "seed", "U_shards"):
        ap.add_argument(f"--{field.replace('_', '-')}", dest=field,
                        type=int, default=None)
    for field in ("lr", "sigma2", "p_max", "eps", "rho", "L"):
        ap.add_argument(f"--{field.replace('_', '-')}", dest=field,
                        type=float, default=None)
    ap.add_argument("--tail", type=int, default=None,
                    help="tail window for <metric>_tail summaries "
                         "(default 10)")
    ap.add_argument("--no-eval", action="store_true",
                    help="skip per-round metric evaluation")
    ap.add_argument("--store", default=None,
                    help="result-store directory (content-hashed cache)")
    ap.add_argument("--csv", default=None,
                    help="write tidy long-format CSV here (default stdout)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the experiment axis over this many devices "
                         "(default: all visible; 1 disables sharding)")
    ap.add_argument("--jobs", type=_parse_jobs, default=1,
                    metavar="N|auto",
                    help="concurrent cohort dispatch threads (async "
                         "runtime; 1 = serial legacy path; 'auto' sizes "
                         "the pool from CostBook measured walls)")
    ap.add_argument("--dispatch-ahead", type=int, default=None,
                    help="extra cohorts allowed in flight beyond --jobs "
                         "(default 2)")
    ap.add_argument("--submit", default=None, metavar="HOST:PORT",
                    help="client mode: post the grid to a running sweep "
                         "service daemon (python -m repro.serve) and "
                         "poll to completion instead of executing "
                         "locally")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address "
                         "(multi-host execution)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="total hosts in a multi-host launch (requires "
                         "--store on a shared filesystem)")
    ap.add_argument("--host-id", type=int, default=None,
                    help="this process's index in [0, --num-hosts) "
                         "(default: $REPRO_HOST_ID or 0)")
    ap.add_argument("--resume", action="store_true",
                    help="pick up a crashed run: sweep tmp debris from "
                         "the store and resume partial cohorts from "
                         "their checkpoints (requires --store)")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    metavar="R",
                    help="checkpoint each cohort's scan carry every R "
                         "rounds under <store>/.runtime/ckpt (requires "
                         "--store; enables --resume to restart "
                         "mid-cohort)")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="re-run a failing cohort up to N times with "
                         "exponential backoff (default 0 = fail fast)")
    ap.add_argument("--retry-backoff", type=float, default=0.5,
                    metavar="SECONDS",
                    help="base backoff before retry k is 2**k times "
                         "this (default 0.5s)")
    ap.add_argument("--quarantine", action="store_true",
                    help="after retries are exhausted, record the "
                         "cohort under <store>/failed/ and keep going "
                         "instead of aborting the sweep (exit code 3 "
                         "when anything was quarantined)")
    ap.add_argument("--lease-timeout", type=float, default=60.0,
                    metavar="SECONDS",
                    help="multi-host: a claim not heartbeated for this "
                         "long is stale and may be stolen (default 60)")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="POINT[:ARG..][!]",
                    help="inject a deterministic fault (repeatable; "
                         "testing only — see repro.runtime.faults)")
    ap.add_argument("--trace", action="store_true",
                    help="record lifecycle spans/events as JSONL under "
                         "<store>/meta/trace (requires --store; export "
                         "with 'python -m repro.obs export <store>'; "
                         "never changes result bytes)")
    ap.add_argument("--flight", action="store_true",
                    help="stream in-flight round telemetry (current "
                         "round, rounds/sec, loss/SNR tail, divergence "
                         "flags) under <store>/meta/flight while cohorts "
                         "run; watch with 'python -m repro.obs watch "
                         "<store>' (requires --store; implies blocked "
                         "execution — defaults --checkpoint-every to "
                         "25; never changes result bytes)")
    ap.add_argument("--sentinel", default=None, metavar="PRED[,PRED..]",
                    help="divergence sentinel predicates for --flight "
                         "(default 'nan'); grammar: nan | "
                         "gap_bound:<margin>:<K> | snr_below:<db>:<K>. "
                         "A trip aborts the cohort between blocks and "
                         "quarantines it with a structured 'diverged' "
                         "record (implies --flight)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of cohort "
                         "execution into DIR (open with Perfetto / "
                         "TensorBoard)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the run's metrics registry snapshot as "
                         "JSON to PATH (same series /metrics serves on "
                         "the daemon)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the cohort + scheduler plan without "
                         "executing")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if not args.spec and not args.axis:
        ap.error("need --spec FILE or at least one --axis NAME=VALUES")
    try:
        spec = build_spec(args)
    except (ValueError, KeyError) as e:
        ap.error(str(e))
    multihost = args.num_hosts > 1 or args.coordinator is not None
    host_id = args.host_id if args.host_id is not None else \
        int(os.environ.get("REPRO_HOST_ID", "0"))
    if args.submit:
        for flag, on in (("--store", args.store is not None),
                         ("--coordinator", args.coordinator is not None),
                         ("--num-hosts", args.num_hosts > 1),
                         ("--resume", args.resume),
                         ("--checkpoint-every",
                          args.checkpoint_every is not None),
                         ("--quarantine", args.quarantine),
                         ("--fault", bool(args.fault)),
                         ("--trace", args.trace),
                         ("--flight", args.flight),
                         ("--sentinel", args.sentinel is not None),
                         ("--profile", args.profile is not None)):
            if on:
                ap.error(f"{flag} is incompatible with --submit: the "
                         f"daemon owns the store and its execution "
                         f"policy")
    if multihost and not args.store and not args.dry_run:
        ap.error("--num-hosts/--coordinator need --store on a shared "
                 "filesystem (every host writes it directly)")
    if not args.store and not args.dry_run:
        for flag, on in (("--resume", args.resume),
                         ("--checkpoint-every",
                          args.checkpoint_every is not None),
                         ("--quarantine", args.quarantine),
                         ("--trace", args.trace),
                         ("--flight", args.flight),
                         ("--sentinel", args.sentinel is not None)):
            if on:
                ap.error(f"{flag} needs --store (it operates on the "
                         f"result store on disk)")
    if args.sentinel is not None:
        args.flight = True
    if args.flight and args.checkpoint_every is None:
        # taps live at blocked-scan boundaries; give them blocks
        args.checkpoint_every = 25
    if args.fault:
        from repro.runtime import faults
        try:
            faults.install(faults.parse(",".join(args.fault)))
        except ValueError as e:
            ap.error(str(e))
    if args.trace:
        trace_lib.install(trace_lib.trace_dir_for(args.store))
        if not args.quiet:
            print(f"# trace: recording lifecycle events under "
                  f"{trace_lib.trace_dir_for(args.store)}",
                  file=sys.stderr)
    else:
        trace_lib.install_from_env()   # $REPRO_TRACE opt-in
    if args.flight:
        from repro.obs import flight as flight_lib
        try:
            flight_lib.install(flight_lib.flight_dir_for(args.store),
                               predicates=args.sentinel)
        except ValueError as e:
            ap.error(str(e))
        if not args.quiet:
            print(f"# flight: streaming round telemetry under "
                  f"{flight_lib.flight_dir_for(args.store)} (sentinel: "
                  f"{args.sentinel or flight_lib.DEFAULT_PREDICATES})",
                  file=sys.stderr)
    else:
        from repro.obs import flight as flight_lib
        flight_lib.install_from_env()  # $REPRO_FLIGHT opt-in
    registry = metrics_lib.Registry(namespace="repro_sweep")

    jobs = args.jobs
    if jobs == "auto":
        from repro.serve import admission as admission_lib
        jobs = admission_lib.auto_jobs(
            store_lib.CostBook(args.store) if args.store else None)
        if not args.quiet:
            print(f"# jobs: auto -> {jobs}", file=sys.stderr)

    cell_list = cells(spec)
    plan = cohorts(cell_list)
    if not args.quiet:
        print(f"# grid: {len(cell_list)} cells in {len(plan)} "
              f"vmappable cohort(s)", file=sys.stderr)
    if args.dry_run:
        for line in format_plan(cell_list, plan):
            print(line, file=sys.stderr)
        if jobs > 1 or multihost:
            for line in format_schedule(plan, jobs,
                                        args.dispatch_ahead,
                                        args.num_hosts):
                print(line, file=sys.stderr)
        return 0

    service_snap = None
    if args.submit:             # the client compiles nothing: no JAX here
        from repro.serve import client as client_lib
        try:
            results, service_snap = client_lib.submit_and_wait(
                args.submit, spec, verbose=not args.quiet)
        except client_lib.ServiceError as e:
            print(f"# service error: {e}", file=sys.stderr)
            return 2
        store = None
    elif multihost:
        compile_cache.enable()
        from repro.runtime import multihost as mh
        results = mh.run_spec_multihost(
            spec, store_root=args.store,
            hs=mh.HostSpec(num_hosts=args.num_hosts, host_id=host_id,
                           coordinator=args.coordinator),
            jobs=jobs, dispatch_ahead=args.dispatch_ahead,
            devices=args.devices, verbose=not args.quiet,
            lease_timeout=args.lease_timeout,
            checkpoint_every=args.checkpoint_every,
            max_retries=args.max_retries,
            retry_backoff=args.retry_backoff,
            quarantine=args.quarantine)
        if results is None:     # non-zero hosts: host 0 collects
            if not args.quiet:
                print(f"# host {host_id}: done (host 0 collects)",
                      file=sys.stderr)
            return 0
        store = store_lib.SweepStore(args.store)   # shared root store
    else:
        compile_cache.enable()
        store = store_lib.SweepStore(args.store) if args.store else None
        if store is not None and not args.resume:
            # startup hygiene: tmp debris older than one lease cannot
            # belong to a live writer (--resume sweeps it all itself)
            store.gc_tmp(args.lease_timeout)
        mesh = shard_lib.sweep_mesh(args.devices)
        with trace_lib.profile(args.profile):
            results = run_spec(spec, store=store, mesh=mesh,
                               jobs=jobs,
                               dispatch_ahead=args.dispatch_ahead,
                               verbose=not args.quiet,
                               resume=args.resume,
                               checkpoint_every=args.checkpoint_every,
                               max_retries=args.max_retries,
                               retry_backoff=args.retry_backoff,
                               quarantine=args.quarantine,
                               registry=registry)

    quarantined = sum(1 for r in results if r is None)
    columns = list(spec.axes)
    rows = store_lib.long_rows([r for r in results if r is not None],
                               columns=columns)
    if args.csv:
        with open(args.csv, "w") as f:
            store_lib.write_long_csv(rows, f)
        if not args.quiet:
            print(f"# wrote {len(rows)} rows to {args.csv}",
                  file=sys.stderr)
    else:
        store_lib.write_long_csv(rows, sys.stdout)
    if store is not None and not args.quiet:
        print(f"# store: {store.root} now holds {len(store)} cells",
              file=sys.stderr)
        health = store.health()
        if health["note_counts"]:
            # corrupt entries read as misses / tmp debris swept — part of
            # the run report, not just scattered stderr warnings
            counts = " ".join(f"{k}={v}" for k, v
                              in sorted(health["note_counts"].items()))
            print(f"# store health: {counts} (affected cells were "
                  f"recomputed; details above)", file=sys.stderr)
    snap = registry.snapshot()
    mispredicted = int(snap.get("engine_costs_mispredicted", 0))
    if mispredicted and not args.quiet:
        print(f"# costbook: {mispredicted} cohort wall(s) deviated >2x "
              f"from the CostBook prediction — schedule estimates for "
              f"this grid are stale (see 'python -m repro.obs report "
              f"{args.store}')", file=sys.stderr)
    if args.metrics_out:
        registry.dump(args.metrics_out)
        if not args.quiet:
            print(f"# metrics: snapshot written to {args.metrics_out}",
                  file=sys.stderr)
    trace_lib.flush()
    from repro.obs import flight as flight_lib
    flight_lib.flush()
    if quarantined and args.submit:
        print(f"# FAILED: {quarantined} cell(s) quarantined/failed by "
              f"the service:", file=sys.stderr)
        for h, msg in sorted((service_snap or {}).get("errors",
                                                      {}).items()):
            print(f"#   {h}: {msg}", file=sys.stderr)
        return 3
    if quarantined:
        from repro.runtime import resilience
        recs = resilience.failed_records(store.root)
        print(f"# FAILED: {quarantined} cell(s) in {len(recs)} "
              f"quarantined cohort(s):", file=sys.stderr)
        for rec in recs:
            err = rec.get("error", {})
            print(f"#   {rec.get('signature')}: "
                  f"{len(rec.get('cells', []))} cell(s), "
                  f"{rec.get('attempts')} attempt(s) — "
                  f"{err.get('type')}: {err.get('message')}",
                  file=sys.stderr)
        print(f"#   records: "
              f"{os.path.join(store.root, resilience.FAILED_DIRNAME)}/ "
              f"(fix and re-run with --resume to heal)", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
