"""``python -m repro.serve`` — run the sweep service daemon.

Examples:

    # serve grids from (and into) sweeps/store on the default port
    python -m repro.serve --store sweeps/store --listen 127.0.0.1:8477

    # auto-tuned pool (default), ephemeral port (printed on stdout)
    python -m repro.serve --store sweeps/store --listen 127.0.0.1:0

Then query it:

    python -m repro.sweep --submit 127.0.0.1:8477 --task linreg \\
        --rounds 10 --axis seed=0:4
    curl -s 127.0.0.1:8477/stats | python -m json.tool
"""

from __future__ import annotations

import argparse
import atexit
import os
import signal
import sys

from repro.obs import flight, logs, trace
from repro.runtime import compile_cache
from repro.serve import api as api_lib
from repro.serve import session as session_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="long-lived sweep service over a result store")
    ap.add_argument("--store", required=True,
                    help="result-store directory served and written")
    ap.add_argument("--listen", default="127.0.0.1:8477",
                    metavar="HOST:PORT",
                    help="bind address (port 0 = ephemeral; the bound "
                         "address is printed on stdout)")
    ap.add_argument("--jobs", default="auto",
                    help="dispatch threads: an integer, or 'auto' "
                         "(default) to size from CostBook measured "
                         "walls + CPU count")
    ap.add_argument("--dispatch-ahead", type=int, default=None,
                    help="extra cohorts in flight beyond --jobs "
                         "(default: auto)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard cohorts over this many devices "
                         "(default: all visible)")
    ap.add_argument("--lease-timeout", type=float, default=60.0,
                    metavar="SECONDS",
                    help="claim-board lease: foreign claims older than "
                         "this are stale and stolen (default 60)")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="retries per failing cohort before quarantine "
                         "(default 1)")
    ap.add_argument("--retry-backoff", type=float, default=0.5)
    ap.add_argument("--max-queued-s", type=float, default=600.0,
                    metavar="SECONDS",
                    help="admission bound: estimated device-seconds one "
                         "client may have queued (default 600)")
    ap.add_argument("--poll-interval", type=float, default=1.0,
                    metavar="SECONDS",
                    help="store poll for foreign-claimed cohorts")
    ap.add_argument("--trace", action="store_true",
                    help="record lifecycle spans/events as JSONL under "
                         "<store>/meta/trace (export with "
                         "'python -m repro.obs export <store>'; never "
                         "changes result bytes)")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    metavar="R",
                    help="run cohorts in checkpointed R-round blocks "
                         "(resumable; also where --flight taps live)")
    ap.add_argument("--flight", action="store_true",
                    help="stream in-flight round telemetry under "
                         "<store>/meta/flight and serve it on GET /live "
                         "(implies blocked execution — defaults "
                         "--checkpoint-every to 25; never changes "
                         "result bytes)")
    ap.add_argument("--sentinel", default=None, metavar="PRED[,PRED..]",
                    help="divergence sentinel predicates for --flight "
                         "(default 'nan'): nan | gap_bound:<margin>:<K> "
                         "| snr_below:<db>:<K>; a trip aborts the "
                         "cohort between blocks into quarantine "
                         "(implies --flight)")
    ap.add_argument("--log-json", action="store_true",
                    help="emit one JSON object per log line (ts, level, "
                         "component, event, ...) instead of plain "
                         "'# component: ...' text")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    host, _, port_s = args.listen.rpartition(":")
    if not host or not port_s.isdigit():
        ap.error(f"--listen wants HOST:PORT, got {args.listen!r}")
    try:
        jobs = int(args.jobs)
    except ValueError:
        if args.jobs != "auto":
            ap.error(f"--jobs wants an integer or 'auto', "
                     f"got {args.jobs!r}")
        jobs = "auto"

    logs.configure(json_mode=args.log_json)
    if args.trace:
        trace.install(trace.trace_dir_for(args.store))
    else:
        trace.install_from_env()   # $REPRO_TRACE opt-in, e.g. under CI

    if os.environ.get("REPRO_FAULTS"):
        # deterministic chaos testing reaches the daemon the same way
        # it reaches the CLI (runtime.faults reads the env on install)
        logs.emit("serve", "faults_active", level="warning",
                  plain="REPRO_FAULTS is set — fault injection active",
                  stream=sys.stderr)

    if args.sentinel is not None:
        args.flight = True
    compile_cache.enable()
    try:
        service = session_lib.SweepService(
            args.store, jobs=jobs, dispatch_ahead=args.dispatch_ahead,
            devices=args.devices, lease_timeout=args.lease_timeout,
            max_retries=args.max_retries,
            retry_backoff=args.retry_backoff,
            max_queued_s_per_client=args.max_queued_s,
            poll_s=args.poll_interval, verbose=not args.quiet,
            checkpoint_every=args.checkpoint_every,
            flight=args.flight, sentinel=args.sentinel)
    except ValueError as e:          # bad --sentinel grammar
        ap.error(str(e))
    server = api_lib.make_server(service, host, int(port_s))

    # graceful flush on orderly stops: the trace recorder buffers up to
    # 64 records / 2s — a SIGTERM (systemd stop, docker stop, CI kill)
    # must not lose that tail.  SystemExit unwinds serve_forever into
    # the finally block below; atexit covers exits that bypass it.
    def _on_term(signum, frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _on_term)
    atexit.register(flight.flush)
    atexit.register(trace.flush)
    bound = server.server_address
    # stdout, flushed: scripts (tests, CI) parse the bound address
    logs.raw(f"listening on {bound[0]}:{bound[1]}")
    if not args.quiet:
        logs.emit("serve", "started",
                  plain=f"store={args.store} jobs={service.engine.jobs} "
                        f"dispatch_ahead={service.engine.dispatch_ahead}",
                  stream=sys.stderr, store=args.store,
                  jobs=service.engine.jobs,
                  dispatch_ahead=service.engine.dispatch_ahead,
                  trace=trace.enabled())
    try:
        server.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        server.server_close()
        service.close()
        trace.flush()
        flight.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
