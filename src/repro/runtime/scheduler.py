"""Concurrent cohort scheduler: cost-ordered dispatch with a bounded
in-flight window, bounded retries, and quarantine.

Two entry points share one machinery:

* :func:`run_cohorts` — the one-shot path: create an engine, submit the
  cohort list as a single batch, wait, tear down.  This is what
  ``run_spec(jobs>=2)`` and the multi-host claim loop call.
* :class:`CohortEngine` — the LONG-LIVED path: a persistent dispatch
  pool + completion writer that accepts many independent batches over
  its lifetime.  The sweep service daemon (``repro.serve``) keeps one
  engine open for days and feeds it a batch per scheduled cohort, so
  repeat grid requests never pay pool/writer startup, and a persistent
  process keeps its jit compile cache warm across requests.

``run_cohorts`` executes a list of sweep cohorts through three
overlapping stages instead of a serial loop:

  dispatch (jobs threads)   prepare_cohort -> trace/compile -> async
                            device dispatch (donated batches); the jit
                            call returns while the computation runs
  device                    up to ``jobs + dispatch_ahead`` cohorts in
                            flight at once (window semaphore)
  writer (1 thread)         device_get + finalize + sink (store writes)
                            as completions become READY, not in
                            submission order

Cohorts are dispatched COSTLIEST FIRST.  The cost is the measured
per-cell wall clock from previous runs when the store's ``CostBook`` has
the cohort's static key (reality beats any model — walls persist across
runs and hosts), falling back to the static ``grid.cohort_cost``
estimate (cells x rounds x U_max x D) rescaled by the median
measured/static ratio so mixed lists compare on one axis.  Ordering and
concurrency never touch numerics: every cohort runs the exact
computation the serial path would, on explicit PRNG keys, so results are
invariant to scheduling (tested in ``tests/test_runtime.py``).

Failure handling is per cohort AND per batch: an error from any stage
(trace, compile, resolve, sink) is retried up to ``max_retries`` times
with exponential backoff; a cohort that exhausts its retries is either
quarantined (structured ``failed/<sig>.json`` record, the REST of the
batch completes) or — the default, preserving the historical contract —
cancels the batch's remaining dispatches, drains its window slots so no
thread deadlocks, and re-raises from :meth:`_Batch.wait`.  A fatal batch
never poisons the engine: other batches (other daemon requests) keep
running on the same pool and writer.

With ``checkpoint_every=R`` cohorts execute through
``grid.run_cohort_blocks`` on the dispatcher thread (R-round blocks,
scan-carry checkpoints under ``<store>/.runtime/ckpt/``), so a killed
process resumes mid-cohort and a retried cohort re-runs only its
unfinished blocks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.obs import trace
from repro.sweep import grid as grid_lib
from repro.sweep import shard as shard_lib
from repro.runtime import faults
from repro.runtime import resilience
from repro.runtime.writer import Completion, CompletionWriter

DEFAULT_DISPATCH_AHEAD = 2

# measured wall beyond this factor (either way) of the schedule-time
# prediction = a mispredict: traced, counted, surfaced in the run report
COST_MISPREDICT_RATIO = 2.0


@dataclasses.dataclass(frozen=True)
class ScheduledCohort:
    """One cohort with its dispatch priority resolved."""

    cohort: grid_lib.Cohort
    cost: float       # measured wall (s) or scaled static estimate
    order: int        # position in the original (grid) cohort list
    measured: bool = False   # cost is a CostBook wall (seconds), not a
                             # rescaled static estimate


def schedule(cohort_list: List[grid_lib.Cohort],
             costs=None) -> List[ScheduledCohort]:
    """Dispatch order: by cost descending, original order as the
    deterministic tie-break (scheduling must be reproducible — debugging
    a concurrent run should never chase a shuffled plan).

    ``costs`` (a ``sweep.store.CostBook``) supplies measured per-cell
    walls by cohort static key; measured cohorts use wall x cells
    directly, unmeasured ones use the static estimate rescaled by the
    median measured/static ratio (identity when nothing is measured).
    """
    static = [float(grid_lib.cohort_cost(co)) for co in cohort_list]
    measured: List[Optional[float]] = []
    for co in cohort_list:
        w = (costs.per_cell_wall(grid_lib.cohort_static_hash(co))
             if costs is not None else None)
        measured.append(None if w is None else w * len(co))
    ratios = sorted(m / s for m, s in zip(measured, static)
                    if m is not None and s > 0)
    scale = ratios[len(ratios) // 2] if ratios else 1.0
    entries = [ScheduledCohort(
        cohort=co,
        cost=(measured[i] if measured[i] is not None
              else static[i] * scale),
        order=i,
        measured=measured[i] is not None)
        for i, co in enumerate(cohort_list)]
    return sorted(entries, key=lambda e: (-e.cost, e.order))


def _tree_ready(out: Any) -> bool:
    """Non-blocking: has every output leaf finished computing?"""
    for leaf in jax.tree.leaves(out):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


class Counters:
    """Thread-safe monotonic event counters (observability only — no
    control flow reads them).

    Optionally backed by an :class:`repro.obs.metrics.Registry`: each
    bump also increments the registry counter ``engine_<name>``, so the
    daemon's ``/metrics`` and the nested ``/stats`` JSON report the same
    events through one write path.
    """

    def __init__(self, registry=None, prefix: str = "engine_"):
        self._lock = threading.Lock()
        self._c: Dict[str, int] = {}
        self._registry = registry
        self._prefix = prefix

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._c[name] = self._c.get(name, 0) + n
        if self._registry is not None:
            self._registry.counter(self._prefix + name).inc(n)

    def get(self, name: str) -> int:
        with self._lock:
            return self._c.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._c)


class _Window:
    """Counting semaphore whose waiters abort on engine shutdown or when
    their batch is cancelled (the ``cancelled`` probe)."""

    def __init__(self, slots: int):
        self._sem = threading.Semaphore(slots)
        self._stop = threading.Event()

    def acquire(self, cancelled: Optional[Callable[[], bool]] = None
                ) -> bool:
        while not self._stop.is_set():
            if cancelled is not None and cancelled():
                return False
            if self._sem.acquire(timeout=0.05):
                return True
        return False

    def release(self) -> None:
        self._sem.release()

    def stop(self) -> None:
        self._stop.set()

    @property
    def stopped(self) -> bool:
        return self._stop.is_set()


class _Batch:
    """Bookkeeping for one submitted cohort list.

    The batch owns everything request-scoped — retry counts, quarantine
    routing, the fatal error, the done event — while the engine owns the
    shared resources (pool, window, writer, mesh context).  A batch that
    fails fast cancels only ITS remaining dispatches; the engine and any
    sibling batches keep running.
    """

    def __init__(self, engine: "CohortEngine", tag: str,
                 entries: List[ScheduledCohort], *,
                 sink: Callable[[grid_lib.Cohort, List[Dict[str, Any]]],
                                None],
                 do_eval: bool, tail: int, eval_data,
                 costs, store_root: Optional[str], cache_key,
                 resume: bool, checkpoint_every: Optional[int],
                 policy: resilience.RetryPolicy,
                 qlog: Optional[resilience.QuarantineLog],
                 qclear: Optional[resilience.QuarantineLog],
                 verbose: bool,
                 on_quarantine: Optional[Callable[[grid_lib.Cohort,
                                                   BaseException, int],
                                                  None]] = None,
                 on_fatal: Optional[Callable[[BaseException],
                                             None]] = None):
        self.engine = engine
        self.tag = tag
        self.entries = entries
        self.sink = sink
        self.do_eval, self.tail, self.eval_data = do_eval, tail, eval_data
        self.costs = costs
        self.store_root, self.cache_key = store_root, cache_key
        self.resume, self.checkpoint_every = resume, checkpoint_every
        self.policy, self.qlog, self.qclear = policy, qlog, qclear
        self.verbose = verbose
        self.on_quarantine, self.on_fatal = on_quarantine, on_fatal

        self._lock = threading.Lock()
        self._outstanding = len(entries)
        self._attempts: Dict[int, int] = {}
        self._fatal: List[BaseException] = []
        self._stop = threading.Event()
        self.done = threading.Event()
        if not entries:
            self.done.set()

    # ----------------------------------------------------------- lifecycle
    def label_of(self, entry: ScheduledCohort) -> str:
        return f"{self.tag}:cohort-{entry.order}"

    @property
    def stopped(self) -> bool:
        return self._stop.is_set()

    def error(self) -> Optional[BaseException]:
        with self._lock:
            return self._fatal[0] if self._fatal else None

    def task_finished(self) -> None:
        with self._lock:
            self._outstanding -= 1
            if self._outstanding <= 0:
                self.done.set()

    def fail_fatal(self, exc: BaseException) -> None:
        with self._lock:
            self._fatal.append(exc)
        self._stop.set()
        self.done.set()     # wake waiters even with work outstanding
        self.engine.counters.bump("batches_failed")
        trace.event("batch.fatal", batch=self.tag,
                    error=type(exc).__name__)
        if self.on_fatal is not None:
            try:
                self.on_fatal(exc)
            except Exception:       # noqa: BLE001 — observability only
                pass

    def wait(self) -> None:
        """Block until every cohort settled; re-raise the first fatal
        error (retry-exhausted without quarantine, or a BaseException)."""
        self.done.wait()
        err = self.error()
        if err is not None:
            raise err

    # ------------------------------------------------------------- failure
    def handle_failure(self, entry: ScheduledCohort,
                       exc: BaseException) -> bool:
        """Retry, quarantine, or declare fatal.  True = handled."""
        with self._lock:
            self._attempts[entry.order] = \
                self._attempts.get(entry.order, 0) + 1
            n = self._attempts[entry.order]
        if n <= self.policy.max_retries and not self.stopped \
                and not self.engine.closed \
                and getattr(exc, "retryable", True):
            pause = self.policy.sleep_for(n - 1)
            if self.verbose:
                print(f"# runtime: cohort {entry.order + 1} failed "
                      f"({type(exc).__name__}: {exc}); retry "
                      f"{n}/{self.policy.max_retries} in {pause:.1f}s",
                      file=sys.stderr)
            self.engine.counters.bump("cohorts_retried")
            trace.event("cohort.retry", cohort=entry.order,
                        batch=self.tag, attempt=n,
                        error=type(exc).__name__, backoff_s=pause)
            timer = threading.Timer(pause, self.engine._resubmit,
                                    args=(self, entry))
            timer.daemon = True
            timer.start()
            return True
        if self.qlog is not None:
            sig = grid_lib.cohort_signature(entry.cohort, self.cache_key)
            path = self.qlog.record(entry.cohort, sig, exc, n,
                                    self.cache_key)
            print(f"# runtime: cohort {entry.order + 1} quarantined "
                  f"after {n} attempt(s) -> {path}", file=sys.stderr)
            self.engine.counters.bump("cohorts_quarantined")
            trace.event("cohort.quarantine", cohort=entry.order,
                        batch=self.tag, attempts=n,
                        error=type(exc).__name__, record=path)
            if self.on_quarantine is not None:
                try:
                    self.on_quarantine(entry.cohort, exc, n)
                except Exception:   # noqa: BLE001 — observability only
                    pass
            self.engine._forget(self.label_of(entry))
            self.task_finished()
            return True
        self.fail_fatal(exc)
        self.engine._forget(self.label_of(entry))
        self.task_finished()
        return False

    # ------------------------------------------------------------ dispatch
    def dispatch_one(self, entry: ScheduledCohort, submitted: float) -> None:
        """Prepare and dispatch one cohort on a pool thread; ``submitted``
        is the ``time.perf_counter()`` at which it was handed to the
        pool."""
        engine = self.engine
        queued_ms = 1e3 * (time.perf_counter() - submitted)
        if self.stopped or self.error() is not None:
            self.task_finished()
            return
        # waiting: queued_ms for a pool thread, the span for a window slot
        with trace.span("cohort.wait", cohort=entry.order, batch=self.tag,
                        queued_ms=queued_ms):
            acquired = engine._window.acquire(cancelled=lambda: self.stopped)
        if not acquired:
            self.task_finished()
            return
        if self.stopped:        # failed while we waited for a slot
            engine._window.release()
            self.task_finished()
            return
        co = entry.cohort
        t0 = time.time()
        try:
            plan_order = entry.order + 1
            faults.fire("kill_at_cohort", cohort=plan_order)
            faults.fire("fail_cohort", cohort=plan_order)
            faults.fire("flaky_cohort", cohort=plan_order)
            if self.verbose:
                print(f"# dispatch cohort {entry.order} x{len(co)} "
                      f"(cost={entry.cost:.3g})", file=sys.stderr)
            engine.counters.bump("cohorts_dispatched")
            if self.checkpoint_every is not None:
                with self._lock:
                    prior = self._attempts.get(entry.order, 0)
                sig = grid_lib.cohort_signature(co, self.cache_key)
                with trace.span("cohort.blocks", cohort=entry.order,
                                batch=self.tag, cells=len(co),
                                every=self.checkpoint_every):
                    results = grid_lib.run_cohort_blocks(
                        co, every=self.checkpoint_every,
                        ckpt_dir=grid_lib.ckpt_dir_for(self.store_root,
                                                       sig),
                        resume=self.resume or prior > 0,
                        do_eval=self.do_eval,
                        tail=self.tail, eval_data=self.eval_data,
                        verbose=self.verbose)

                def resolve_fn(results=results, entry=entry, t0=t0):
                    if self.stopped:
                        return None
                    faults.delay("delay_resolve")
                    self._record_cost(entry, t0)
                    return results

                ready_fn = None             # already on host: FIFO-ready
            else:
                with trace.span("cohort.prepare", cohort=entry.order,
                                batch=self.tag, cells=len(co)):
                    prep = grid_lib.prepare_cohort(
                        co, do_eval=self.do_eval,
                        eval_data=self.eval_data)
                with trace.span("cohort.dispatch", cohort=entry.order,
                                batch=self.tag, cells=len(co),
                                cost=entry.cost):
                    out, e = shard_lib.dispatch_sharded(
                        jax.vmap(prep.run_one), prep.batch,
                        engine._mesh, donate=True)

                def resolve_fn(out=out, e=e, co=co, entry=entry, t0=t0):
                    if self.stopped:
                        return None
                    faults.delay("delay_resolve")
                    with trace.span("cohort.resolve",
                                    cohort=entry.order, batch=self.tag,
                                    cells=len(co)):
                        host = shard_lib.resolve(out, e)
                        host = {k: np.asarray(v)
                                for k, v in host.items()}
                        res = grid_lib.finalize_cohort(co, host,
                                                       tail=self.tail)
                    self._record_cost(entry, t0)
                    return res

                ready_fn = (lambda out=out: _tree_ready(out))
        except BaseException as exc:   # noqa: BLE001 — routed per policy
            engine._window.release()
            if isinstance(exc, Exception):
                self.handle_failure(entry, exc)
            else:
                self.fail_fatal(exc)
                self.task_finished()
            return

        def sink_fn(results, co=co, entry=entry):
            if results is None or self.stopped:   # cancelled in flight
                self.engine._forget(self.label_of(entry))
                self.task_finished()
                return
            self.sink(co, results)
            if self.qclear is not None:
                # the cohort succeeded; a record from an earlier run or
                # another host's exhausted retries is obsolete
                self.qclear.clear(
                    grid_lib.cohort_signature(co, self.cache_key))
            self.engine.counters.bump("cohorts_completed")
            self.engine._forget(self.label_of(entry))
            self.task_finished()

        engine._writer.submit(Completion(
            label=self.label_of(entry),
            resolve=resolve_fn,
            sink=sink_fn,
            ready=ready_fn,
            release=engine._window.release))

    def _record_cost(self, entry: ScheduledCohort, t0: float) -> None:
        # dispatch-start -> resolve-end: includes compile + any queueing
        # overlap, which is exactly the wall a future scheduler pays
        co = entry.cohort
        wall = time.time() - t0
        hist = self.engine._wall_hist
        if hist is not None:
            hist.observe(wall)
        # accuracy guard: only meaningful against a MEASURED prediction
        # (seconds); the rescaled static estimate is an ordering key, not
        # a wall forecast
        if entry.measured and entry.cost > 0 and wall > 0:
            ratio = wall / entry.cost
            if ratio > COST_MISPREDICT_RATIO \
                    or ratio < 1.0 / COST_MISPREDICT_RATIO:
                self.engine.counters.bump("costs_mispredicted")
                trace.event("cost.mispredict", cohort=entry.order,
                            batch=self.tag, predicted_s=entry.cost,
                            measured_s=wall, ratio=ratio)
        if self.costs is not None:
            self.costs.record(
                grid_lib.cohort_static_hash(co), wall_s=wall,
                cells=len(co),
                predicted_s=entry.cost if entry.measured else None)


class CohortEngine:
    """A reusable cohort execution engine: one dispatch pool, one
    in-flight window, one completion writer — shared by every batch
    submitted over the engine's lifetime.

    ``run_cohorts`` opens one for a single batch and closes it; the
    sweep service daemon (``repro.serve.session``) keeps one open for
    its whole life, so concurrent grid requests share the concurrency
    bound (``jobs + dispatch_ahead`` cohorts holding device buffers,
    daemon-wide) and the process-level jit cache stays warm across
    requests.
    """

    def __init__(self, *, jobs: int,
                 dispatch_ahead: Optional[int] = None,
                 mesh=None, verbose: bool = False, registry=None):
        if jobs < 1:
            raise ValueError(f"jobs must be >= 1, got {jobs}")
        if dispatch_ahead is None:
            dispatch_ahead = DEFAULT_DISPATCH_AHEAD
        if dispatch_ahead < 0:
            raise ValueError(
                f"dispatch_ahead must be >= 0, got {dispatch_ahead}")
        self.jobs = jobs
        self.dispatch_ahead = dispatch_ahead
        self.registry = registry
        self.counters = Counters(registry=registry)
        self.closed = False
        self._mesh = mesh
        self._window = _Window(jobs + dispatch_ahead)
        self._writer = CompletionWriter(on_error=self._route_error)
        self._wall_hist = None
        if registry is not None:
            self._wall_hist = registry.histogram(
                "engine_cohort_wall_seconds",
                "dispatch-start to resolve-end wall per cohort")
            registry.gauge("engine_writer_queue_depth",
                           "completions submitted but not retired",
                           fn=self._writer.pending)
        self._labels: Dict[str, Tuple[_Batch, ScheduledCohort]] = {}
        self._labels_lock = threading.Lock()
        self._seq = itertools.count()
        # hold the mesh context across the whole pool: per-dispatch
        # nesting from worker threads then always restores to this same
        # mesh, so one thread's context exit can never deactivate it
        # under another
        self._stack = contextlib.ExitStack()
        if mesh is not None:
            self._stack.enter_context(jax.set_mesh(mesh))
        self._pool = ThreadPoolExecutor(
            max_workers=jobs, thread_name_prefix="sweep-dispatch")

    # -------------------------------------------------------------- public
    def submit(self, cohort_list: List[grid_lib.Cohort], *,
               sink: Callable[[grid_lib.Cohort, List[Dict[str, Any]]],
                              None],
               do_eval: bool = True, tail: int = 10, eval_data=None,
               costs=None, store_root: Optional[str] = None,
               cache_key=None, resume: bool = False,
               checkpoint_every: Optional[int] = None,
               max_retries: int = 0, retry_backoff: float = 0.5,
               quarantine: bool = False, verbose: bool = False,
               on_quarantine=None, on_fatal=None) -> _Batch:
        """Schedule ``cohort_list`` as one batch; returns its handle.

        ``sink(cohort, results)`` fires on the writer thread as each
        cohort's results reach host memory; ``on_quarantine(cohort, exc,
        attempts)`` / ``on_fatal(exc)`` are optional observability hooks
        for callers that cannot block in :meth:`_Batch.wait` (the
        daemon).  On success every cohort has been sunk exactly once.
        """
        if self.closed:
            raise RuntimeError("engine is closed")
        if checkpoint_every is not None and store_root is None:
            raise ValueError("checkpoint_every requires store_root")
        entries = schedule(cohort_list, costs=costs)
        policy = resilience.RetryPolicy(max_retries=max_retries,
                                        backoff_s=retry_backoff)
        qclear = (resilience.QuarantineLog(store_root)
                  if store_root is not None else None)
        batch = _Batch(self, f"b{next(self._seq)}", entries, sink=sink,
                       do_eval=do_eval, tail=tail, eval_data=eval_data,
                       costs=costs, store_root=store_root,
                       cache_key=cache_key, resume=resume,
                       checkpoint_every=checkpoint_every, policy=policy,
                       qlog=(qclear if quarantine else None),
                       qclear=qclear, verbose=verbose,
                       on_quarantine=on_quarantine, on_fatal=on_fatal)
        with self._labels_lock:
            for e in entries:
                self._labels[batch.label_of(e)] = (batch, e)
        self.counters.bump("batches_submitted")
        trace.event("batch.submit", batch=batch.tag,
                    cohorts=len(entries),
                    cells=sum(len(e.cohort) for e in entries),
                    measured=sum(1 for e in entries if e.measured))
        submitted = time.perf_counter()
        for e in entries:
            self._pool.submit(batch.dispatch_one, e, submitted)
        return batch

    def pending(self) -> int:
        """Completions submitted to the writer but not yet retired."""
        return self._writer.pending()

    def close(self) -> None:
        """Join the pool, drain the writer, release the mesh context.
        Re-raises a writer-level fatal (BaseException) if one occurred."""
        self.closed = True
        self._window.stop()
        self._pool.shutdown(wait=True)
        try:
            self._writer.close()
        finally:
            self._stack.close()

    # ------------------------------------------------------------ internal
    def _resubmit(self, batch: _Batch, entry: ScheduledCohort) -> None:
        if batch.stopped or self.closed:
            batch.task_finished()
            return
        try:
            self._pool.submit(batch.dispatch_one, entry,
                              time.perf_counter())
        except RuntimeError:            # pool already shut down
            batch.task_finished()

    def _forget(self, label: str) -> None:
        with self._labels_lock:
            self._labels.pop(label, None)

    def _route_error(self, completion: Completion,
                     exc: BaseException) -> bool:
        """Writer ``on_error``: route to the owning batch.  Always
        returns True for a known label — even a batch-fatal error is
        recorded on the BATCH (re-raised from its ``wait``), so the
        shared writer never goes sticky and sibling batches survive."""
        with self._labels_lock:
            item = self._labels.get(completion.label)
        if item is None:
            return False    # unknown label: engine bug, fail loudly
        batch, entry = item
        try:
            batch.handle_failure(entry, exc)
        except BaseException as cb_exc:  # noqa: BLE001 — must not wedge
            batch.fail_fatal(cb_exc)
            batch.task_finished()
        return True


def run_cohorts(cohort_list: List[grid_lib.Cohort], *,
                sink: Callable[[grid_lib.Cohort, List[Dict[str, Any]]],
                               None],
                jobs: int, dispatch_ahead: Optional[int] = None,
                do_eval: bool = True, tail: int = 10, mesh=None,
                eval_data=None, verbose: bool = False,
                costs=None, store_root: Optional[str] = None,
                cache_key=None, resume: bool = False,
                checkpoint_every: Optional[int] = None,
                max_retries: int = 0, retry_backoff: float = 0.5,
                quarantine: bool = False, registry=None) -> None:
    """Run every cohort concurrently; ``sink(cohort, results)`` fires on
    the writer thread as each cohort's results reach host memory.

    One-shot wrapper over :class:`CohortEngine`: ``jobs`` dispatcher
    threads each drive prepare -> compile -> async dispatch; at most
    ``jobs + dispatch_ahead`` cohorts hold device buffers at once.  A
    failing cohort is retried ``max_retries`` times (backoff
    ``retry_backoff * 2**attempt`` seconds) and then either quarantined
    (``quarantine=True`` + ``store_root``) or — the default — the first
    error cancels the rest and re-raises here.  On success every cohort
    has been sunk exactly once.

    Fault-plan cohort points (``kill_at_cohort`` etc.) address cohorts
    by their 1-based position in ``cohort_list`` — the PLAN order, which
    is identical for the serial path and any ``jobs`` setting.
    """
    if jobs < 1:
        raise ValueError(f"jobs must be >= 1, got {jobs}")
    if checkpoint_every is not None and store_root is None:
        raise ValueError("checkpoint_every requires store_root")
    if not cohort_list:
        return
    engine = CohortEngine(jobs=jobs, dispatch_ahead=dispatch_ahead,
                          mesh=mesh, verbose=verbose, registry=registry)
    err: Optional[BaseException] = None
    try:
        batch = engine.submit(
            cohort_list, sink=sink, do_eval=do_eval, tail=tail,
            eval_data=eval_data, costs=costs, store_root=store_root,
            cache_key=cache_key, resume=resume,
            checkpoint_every=checkpoint_every, max_retries=max_retries,
            retry_backoff=retry_backoff, quarantine=quarantine,
            verbose=verbose)
        batch.wait()
    except BaseException as e:   # noqa: BLE001 — re-raised after close
        err = e
    try:
        engine.close()
    except BaseException as e:   # noqa: BLE001 — first error wins
        if err is None:
            err = e
    if err is not None:
        raise err
