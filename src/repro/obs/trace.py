"""Lifecycle tracing: thread-safe span/event recorder + Chrome export.

A :class:`TraceRecorder` appends one JSON object per line to a file
under its trace directory (by convention ``<store>/meta/trace/``), in
Chrome trace-event shape so export is a pure re-wrap:

    {"name": "cohort.dispatch", "cat": "runtime", "ph": "X",
     "ts": <epoch microseconds>, "dur": <microseconds>,
     "pid": <os pid>, "tid": <thread id>, "args": {...}}

``ph`` is ``"X"`` for complete spans and ``"i"`` for instant events.
:func:`export_chrome` folds every ``*.jsonl`` file in one or more trace
directories into one ``{"traceEvents": [...]}`` document loadable in
Perfetto or ``chrome://tracing``.  Each recorder opens its file with a
``clock_sync`` metadata record (host, epoch vs monotonic clock at open),
so a merged multi-process export can name per-pid/host lanes, correct
same-host wall-clock skew against the monotonic clock, and draw flow
arrows from a cohort claim's original holder to the host that stole it.

The module-level API (:func:`span` / :func:`event`) is what the runtime
is instrumented with.  A record goes to two sinks:

* the installed recorder's JSONL file, as above;
* a running ``jax.profiler`` capture: the span opens a profiler
  annotation (``jax.profiler.TraceAnnotation``), so it lands in the
  ``.xplane.pb`` as a host event on the device trace's clock with its
  args as stats, and the record is appended to a bounded in-process
  buffer that :func:`captured` returns.  A harness that profiles a
  window reads that window's spans from there after the capture stops.

With neither a recorder nor a capture, both are no-ops (one attribute
read and one ``is_enabled`` call), so the traced and untraced code paths
execute the identical computation — tracing can never change result
bytes, only add files under ``meta/``.  Span durations come from
``time.perf_counter``; ``ts`` is epoch microseconds.

Install via :func:`install` (the CLI's ``--trace`` / the daemon's
``--trace``) or the ``REPRO_TRACE`` environment variable (a directory
path), which lets subprocess tests and chaos runs trace without
plumbing flags.
"""

from __future__ import annotations

import contextlib
import json
import os
import socket
import threading
import time
from collections import deque
from typing import (Any, Dict, Iterator, List, Optional, Sequence,
                    Tuple, Union)

try:
    # the profiler's annotation class (``jax.profiler.TraceAnnotation``
    # subclasses it and adds nothing); jaxlib is imported without jax, so
    # ``python -m repro.obs`` stays light
    from jaxlib._profiler import TraceMe as _Annotation
    _capturing = _Annotation.is_enabled
except ImportError:                      # no jaxlib: no profiler to feed
    _Annotation = None

    def _capturing() -> bool:
        return False

ENV_VAR = "REPRO_TRACE"
TRACE_DIRNAME = os.path.join("meta", "trace")
#: records kept by the in-process capture; the oldest go first
CAPTURE_MAX = 65536

_lock = threading.Lock()
_rec: Optional["TraceRecorder"] = None
_capture_lock = threading.Lock()
_capture: deque = deque(maxlen=CAPTURE_MAX)


def trace_dir_for(store_root: str) -> str:
    """The canonical trace directory of a store (under ``meta/`` so
    byte-identity diffs exclude it)."""
    return os.path.join(store_root, TRACE_DIRNAME)


class TraceRecorder:
    """Thread-safe append-only recorder of spans and instant events.

    One recorder writes one ``trace-<pid>-<seq>.jsonl`` file; concurrent
    processes (multi-host sweeps, a daemon next to a CLI run) each write
    their own file in the shared directory and the exporter merges them.
    Record calls buffer under a lock and flush every ``flush_every``
    records (and on :meth:`close`), so the hot path is append + occasional
    write, never a per-span fsync.
    """

    def __init__(self, trace_dir: str, *, flush_every: int = 64,
                 flush_after_s: float = 2.0):
        os.makedirs(trace_dir, exist_ok=True)
        self.dir = trace_dir
        self.pid = os.getpid()
        self._lock = threading.Lock()
        self._buf: List[str] = []
        self._flush_every = max(1, flush_every)
        # long-lived daemons record sparsely: age out the buffer so a
        # hard kill (SIGTERM, no finally) loses at most a few seconds
        self._flush_after_s = flush_after_s
        self._last_flush = time.time()
        self._closed = False
        # unique per (pid, open): a respawned pid never appends to a
        # previous life's file mid-line
        seq = 0
        while True:
            name = f"trace-{self.pid}-{seq}.jsonl"
            self.path = os.path.join(trace_dir, name)
            try:
                fd = os.open(self.path,
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
                break
            except FileExistsError:
                seq += 1
        # clock-sync metadata opens every file: pairs this process's
        # wall clock with its monotonic clock so a multi-process merge
        # can align same-host lanes skew-free (``export_chrome``) and
        # label lanes by host.  ``ph: "M"`` records are metadata — the
        # default ``load_events`` skips them.
        self._emit({"name": "clock_sync", "ph": "M", "pid": self.pid,
                    "tid": 0, "ts": int(time.time() * 1e6),
                    "args": {"host": socket.gethostname(),
                             "epoch_us": int(time.time() * 1e6),
                             "mono_us": int(time.monotonic() * 1e6)}})

    # ------------------------------------------------------------ recording
    def _emit(self, rec: Dict[str, Any]) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str)
        with self._lock:
            if self._closed:
                return
            self._buf.append(line)
            if (len(self._buf) >= self._flush_every
                    or time.time() - self._last_flush
                    >= self._flush_after_s):
                self._flush_locked()

    def event(self, name: str, cat: str = "runtime",
              **args: Any) -> None:
        """Record one instant event (Chrome ``ph: "i"``)."""
        _event(self, name, cat, args)

    def span(self, name: str, cat: str = "runtime", **args: Any):
        """Record a complete span (``ph: "X"``) around a block.

        Yields the mutable ``args`` dict so the block can attach results
        discovered mid-span (e.g. the number of cells finalized).  The
        span is recorded even when the block raises, with
        ``args["error"]`` naming the exception type.
        """
        return _span(self, name, cat, args)

    # ------------------------------------------------------------ lifecycle
    def _flush_locked(self) -> None:
        if not self._buf:
            return
        with open(self.path, "a") as f:
            f.write("\n".join(self._buf) + "\n")
        self._buf = []
        self._last_flush = time.time()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            self._closed = True


# ------------------------------------------------------- module-level API

def install(trace_dir: str) -> TraceRecorder:
    """Install a process-global recorder writing under ``trace_dir``.
    Idempotent per directory: re-installing the same directory keeps the
    existing recorder (one file per process life)."""
    global _rec
    with _lock:
        if _rec is not None and _rec.dir == trace_dir:
            return _rec
        if _rec is not None:
            _rec.close()
        _rec = TraceRecorder(trace_dir)
        return _rec


def install_from_env() -> Optional[TraceRecorder]:
    """Install from ``$REPRO_TRACE`` (a trace directory) when set —
    how subprocesses (chaos tests, multi-host workers) opt in."""
    d = os.environ.get(ENV_VAR)
    return install(d) if d else None


def uninstall() -> None:
    global _rec
    with _lock:
        if _rec is not None:
            _rec.close()
        _rec = None


def installed() -> Optional[TraceRecorder]:
    return _rec


def enabled() -> bool:
    return _rec is not None


def _record(rec: Optional["TraceRecorder"], capture: bool,
            record: Dict[str, Any]) -> None:
    if rec is not None:
        rec._emit(record)
    if capture:
        with _capture_lock:
            _capture.append(record)


def _event(rec: Optional["TraceRecorder"], name: str, cat: str,
           args: Dict[str, Any]) -> None:
    capture = _capturing()
    if capture:
        with _Annotation(name, **args):     # a zero-length host event
            pass
    _record(rec, capture, {
        "name": name, "cat": cat, "ph": "i", "s": "t",
        "ts": int(time.time() * 1e6), "pid": os.getpid(),
        "tid": threading.get_ident(), "args": args})


@contextlib.contextmanager
def _span(rec: Optional["TraceRecorder"], name: str, cat: str,
          args: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
    # a span that opens inside a profiler capture goes to the capture
    capture = _capturing()
    annotation = _Annotation(name, **args) if capture else None
    opening = set(args) if capture else ()
    ts = time.time()
    if annotation is not None:
        annotation.__enter__()
    t0 = time.perf_counter()
    try:
        yield args
    except BaseException as e:
        args["error"] = type(e).__name__
        raise
    finally:
        dur = time.perf_counter() - t0
        if annotation is not None:
            late = {k: v for k, v in args.items() if k not in opening}
            if late:                        # args attached mid-span
                annotation.set_metadata(**late)
            annotation.__exit__(None, None, None)
        _record(rec, capture, {
            "name": name, "cat": cat, "ph": "X", "ts": int(ts * 1e6),
            "dur": max(0, int(dur * 1e6)), "pid": os.getpid(),
            "tid": threading.get_ident(), "args": args})


def event(name: str, cat: str = "runtime", **args: Any) -> None:
    """Record an instant event on the installed recorder and in a
    running profiler capture (no-op when neither is on)."""
    rec = _rec
    if rec is not None or _capturing():
        _event(rec, name, cat, args)


_NULL_ARGS: Dict[str, Any] = {}
_NULL_SPAN = contextlib.nullcontext(_NULL_ARGS)


def span(name: str, cat: str = "runtime", **args: Any):
    """Span context manager on the installed recorder and a running
    profiler capture; the shared no-op :data:`_NULL_SPAN` when neither
    is on (the untraced path stays allocation-free)."""
    rec = _rec
    if rec is None and not _capturing():
        return _NULL_SPAN
    return _span(rec, name, cat, args)


def captured() -> List[Dict[str, Any]]:
    """The records of every span and event that opened while a
    ``jax.profiler`` capture ran in this process (oldest first, at most
    :data:`CAPTURE_MAX`), in the recorder's record shape: ``name``,
    ``cat``, ``ph`` (``"X"`` span, ``"i"`` event), ``ts``, ``dur`` (spans;
    microseconds) and ``args``."""
    with _capture_lock:
        return list(_capture)


def clear_captured() -> None:
    """Forget the records :func:`captured` holds."""
    with _capture_lock:
        _capture.clear()


def flush() -> None:
    rec = _rec
    if rec is not None:
        rec.flush()


# -------------------------------------------------------------- profiling

@contextlib.contextmanager
def profile(profile_dir: Optional[str]) -> Iterator[None]:
    """Opt-in ``jax.profiler`` capture around a block (``--profile DIR``).

    ``None`` is a no-op.  The capture wraps cohort dispatch/execution, so
    the XLA-level timeline (compile, fusion, device compute) holds the
    lifecycle spans as host events — load the output in TensorBoard or
    Perfetto.
    """
    if not profile_dir:
        yield
        return
    import jax
    os.makedirs(profile_dir, exist_ok=True)
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------- reading

def _load_file(path: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    try:
        f = open(path)
    except OSError:
        return out
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _trace_files(trace_dirs: Union[str, Sequence[str]]) -> List[str]:
    dirs = ([trace_dirs] if isinstance(trace_dirs, str)
            else list(trace_dirs))
    paths: List[str] = []
    for d in dirs:
        if not os.path.isdir(d):
            continue
        paths.extend(os.path.join(d, fn) for fn in sorted(os.listdir(d))
                     if fn.endswith(".jsonl"))
    return paths


def load_events(trace_dirs: Union[str, Sequence[str]],
                include_meta: bool = False) -> List[Dict[str, Any]]:
    """Every record from every ``*.jsonl`` file under one or more trace
    directories, sorted by timestamp.  Unparseable lines (a live
    writer's partial tail) are skipped — reading a trace must never fail
    a run.  Metadata records (``ph: "M"``, e.g. ``clock_sync``) are
    skipped unless ``include_meta``."""
    out: List[Dict[str, Any]] = []
    for path in _trace_files(trace_dirs):
        for rec in _load_file(path):
            if include_meta or rec.get("ph") != "M":
                out.append(rec)
    out.sort(key=lambda r: r.get("ts", 0))
    return out


def load_sync(trace_dirs: Union[str, Sequence[str]]
              ) -> Dict[int, Dict[str, Any]]:
    """Per-pid clock-sync metadata (host, epoch/monotonic pairing at
    recorder open) from one or more trace directories."""
    out: Dict[int, Dict[str, Any]] = {}
    for path in _trace_files(trace_dirs):
        for rec in _load_file(path):
            if rec.get("ph") == "M" and rec.get("name") == "clock_sync":
                args = rec.get("args") or {}
                pid = rec.get("pid")
                if isinstance(pid, int) and pid not in out:
                    out[pid] = {"host": args.get("host", "?"),
                                "epoch_us": args.get("epoch_us"),
                                "mono_us": args.get("mono_us")}
    return out


#: trace-event names that anchor claim-steal flow arrows: the source
#: side last touched the claim; the destination side took it over.
_FLOW_SRC = ("claim.acquire", "claim.release")
_FLOW_DST = ("claim.steal", "session.steal")


def _claim_flows(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Chrome flow-event pairs (``ph: "s"`` / ``"f"``) from one process's
    claim on a cohort to the process that stole it — the work-stealing
    handoff drawn as an arrow across lanes."""
    last_touch: Dict[str, Dict[str, Any]] = {}
    flows: List[Dict[str, Any]] = []
    fid = 0
    for ev in events:
        sig = (ev.get("args") or {}).get("sig")
        if not sig:
            continue
        name = ev.get("name")
        if name in _FLOW_SRC:
            last_touch[sig] = ev
        elif name in _FLOW_DST:
            src = last_touch.get(sig)
            if src is not None and src.get("pid") != ev.get("pid"):
                fid += 1
                common = {"cat": "claim", "name": "claim-steal",
                          "id": fid, "args": {"sig": sig}}
                flows.append({**common, "ph": "s", "pid": src["pid"],
                              "tid": src.get("tid", 0),
                              "ts": src.get("ts", 0)})
                flows.append({**common, "ph": "f", "bp": "e",
                              "pid": ev.get("pid"),
                              "tid": ev.get("tid", 0),
                              "ts": ev.get("ts", 0)})
            # the thief now holds the claim: further steals arrow from it
            last_touch[sig] = ev
    return flows


def export_chrome(trace_dirs: Union[str, Sequence[str]]
                  ) -> Dict[str, Any]:
    """Fold one or more trace directories into one Chrome trace-event
    document.

    The records are already trace-event shaped; the export re-bases
    timestamps to the earliest event (Perfetto prefers small ``ts``) and
    wraps them with the container keys viewers expect.  When the trace
    spans multiple processes (an elastic multi-host run, a daemon next
    to CLI runs), the merge additionally

    * aligns same-host lanes on their monotonic-clock offsets (each
      file's ``clock_sync`` record pairs the wall and monotonic clocks
      at open, so wall-clock skew between two processes of one host
      cancels out),
    * names per-pid lanes ``<host> pid <pid>`` via ``process_name``
      metadata, and
    * draws claim-steal flow arrows (``ph: "s"/"f"``) from the process
      that held a cohort's claim to the one that stole it.
    """
    events = load_events(trace_dirs)
    sync = load_sync(trace_dirs)
    pids = sorted({e["pid"] for e in events if "pid" in e})

    if len(pids) > 1 and sync:
        # same-host skew correction: every process records
        # (epoch - mono) at open; on one host the monotonic clocks share
        # a base, so differences in that offset ARE wall-clock skew
        by_host: Dict[str, List[Tuple[int, float]]] = {}
        for pid, s in sync.items():
            if s["epoch_us"] is not None and s["mono_us"] is not None:
                by_host.setdefault(s["host"], []).append(
                    (pid, s["epoch_us"] - s["mono_us"]))
        shift: Dict[int, float] = {}
        for host, offsets in by_host.items():
            ref = min(off for _, off in offsets)
            for pid, off in offsets:
                if off != ref:
                    shift[pid] = off - ref
        if shift:
            for e in events:
                if "ts" in e and e.get("pid") in shift:
                    e["ts"] = e["ts"] - shift[e["pid"]]
            events.sort(key=lambda r: r.get("ts", 0))

    if len(pids) > 1:
        events.extend(_claim_flows(events))
        for sort_index, pid in enumerate(pids):
            host = sync.get(pid, {}).get("host", "?")
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid,
                           "args": {"name": f"{host} pid {pid}"}})
            events.append({"name": "process_sort_index", "ph": "M",
                           "pid": pid,
                           "args": {"sort_index": sort_index}})

    t0 = min((e["ts"] for e in events if "ts" in e), default=0)
    for e in events:
        if "ts" in e:
            e["ts"] = e["ts"] - t0
    hosts = sorted({s["host"] for s in sync.values()}) or None
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"source": "repro.obs.trace",
                          "epoch_us": t0,
                          **({"hosts": hosts,
                              "processes": len(pids)}
                             if len(pids) > 1 else {})}}
