"""Session layer of the sweep service: request lifecycle, in-flight
cohort dedup, and streaming per-cell completion.

A :class:`SweepService` owns ONE persistent :class:`~repro.runtime.
scheduler.CohortEngine` (dispatch pool + completion writer + mesh
context, alive for the daemon's whole life) over ONE
:class:`~repro.sweep.store.SweepStore`.  Each submitted
:class:`~repro.sweep.grid.SweepSpec` is classified cell by cell under
the service lock:

  hit        the store already holds the cell — served immediately, no
             device work, no scheduler contact;
  shared     an in-flight cohort (from ANY earlier request) already
             covers the cell — the request subscribes to its completion
             instead of scheduling a duplicate;
  scheduled  a genuinely new cell — new cells regroup into cohorts, each
             cohort is claimed on the store's work-stealing claim board
             and dispatched through the engine;
  waiting    the claim board says another PROCESS (a one-shot CLI run, a
             sibling daemon on the shared store) holds a live lease on
             the cohort — the service watches the store and streams
             cells in as the foreign worker lands them, stealing the
             claim if its lease goes stale.

Results are delivered in the store's own document shape (the ``result``
field of ``<hash>.json``): computed cells are written through
``SweepStore.put`` first and read back, so a served document is
byte-derived from exactly what a one-shot ``python -m repro.sweep`` run
would have put there — the byte-identity invariant extends to the
service tier.  Admission (see :mod:`repro.serve.admission`) is checked
before any state mutates, so a rejected request leaves no residue.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Set

from repro.obs import flight as flight_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as obs_trace
from repro.runtime import resilience
from repro.runtime.claims import ClaimBoard
from repro.runtime.scheduler import CohortEngine
from repro.serve import admission as admission_lib
from repro.sweep import grid as grid_lib
from repro.sweep import shard as shard_lib
from repro.sweep import store as store_lib

# session counter -> registry series name, where they differ (the
# nested /stats JSON and the flat Prometheus names predate the registry
# and both are pinned by consumers)
_METRIC_ALIAS = {"claims_stolen": "claims_stolen_from_foreign"}


def spec_from_doc(doc: Any) -> grid_lib.SweepSpec:
    """Build a SweepSpec from its wire/JSON form — the same document
    shape ``python -m repro.sweep --spec file.json`` reads."""
    if not isinstance(doc, dict) or not isinstance(doc.get("axes"), dict):
        raise ValueError("spec document needs an 'axes' mapping")
    return grid_lib.SweepSpec(
        axes={k: list(v) for k, v in doc["axes"].items()},
        base=dict(doc.get("base", {})),
        eval=bool(doc.get("eval", True)),
        tail=int(doc.get("tail", 10)))


def spec_to_doc(spec: grid_lib.SweepSpec) -> Dict[str, Any]:
    return {"axes": {k: list(v) for k, v in spec.axes.items()},
            "base": store_lib.jsonable(dict(spec.base)),
            "eval": spec.eval, "tail": spec.tail}


class Request:
    """One submitted grid: per-cell status, streamed results, terminal
    state.  All mutation happens under the service lock."""

    def __init__(self, rid: str, spec: grid_lib.SweepSpec,
                 cell_list: List[Dict[str, Any]], hashes: List[str],
                 cache_key: Dict[str, Any], client: str):
        self.id = rid
        self.spec = spec
        self.client = client
        self.created = time.time()
        self.cells = cell_list
        self.hashes = hashes                      # grid order
        self.cache_key = cache_key
        self.status: Dict[str, str] = {}
        self.results: Dict[str, Dict[str, Any]] = {}
        self.errors: Dict[str, str] = {}
        self._pending: Set[str] = set()
        self.done = threading.Event()

    def mark_pending(self, h: str, status: str) -> None:
        self.status[h] = status
        self._pending.add(h)

    def deliver(self, h: str, doc: Dict[str, Any]) -> None:
        self.results[h] = doc
        self.status[h] = "done"
        self._settle(h)

    def deliver_hit(self, h: str, doc: Dict[str, Any]) -> None:
        self.results[h] = doc
        self.status[h] = "hit"

    def mark_terminal(self, h: str, status: str, msg: str) -> None:
        self.status[h] = status
        self.errors[h] = msg
        self._settle(h)

    def _settle(self, h: str) -> None:
        self._pending.discard(h)
        if not self._pending:
            self.done.set()

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for h in self.hashes:
            s = self.status.get(h, "unknown")
            out[s] = out.get(s, 0) + 1
        return out

    def state(self) -> str:
        return "done" if self.done.is_set() else "running"

    def snapshot(self, include_results: bool = False) -> Dict[str, Any]:
        doc: Dict[str, Any] = {
            "id": self.id,
            "state": self.state(),
            "counts": self.counts(),
            "cells": [{"hash": h, "status": self.status.get(h, "unknown")}
                      for h in self.hashes],
            "quarantined": sorted(h for h, s in self.status.items()
                                  if s == "quarantined"),
            "failed": sorted(h for h, s in self.status.items()
                             if s == "failed"),
            "errors": dict(self.errors),
        }
        if include_results:
            doc["results"] = {h: self.results[h] for h in self.results}
        return doc


class _Inflight:
    """One cohort being computed (by our engine or a foreign process),
    with the requests subscribed to its completion."""

    def __init__(self, sig: str, cohort, cache_key, *,
                 kind: str, client: str, est_s: float):
        self.sig = sig
        self.cohort = cohort
        self.cache_key = cache_key
        self.kind = kind                  # "scheduled" | "waiting"
        self.client = client
        self.est_s = est_s                # admission charge (ours only)
        self.subscribers: List[Request] = []
        self.hashes = [store_lib.cell_hash(c, cache_key)
                       for c in cohort.cells]
        self.remaining: Set[str] = set(self.hashes)


class SweepService:
    """The daemon's brain: classify, dedup, admit, dispatch, stream.

    Thread model: HTTP handler threads call :meth:`submit` /
    :meth:`request_snapshot` / :meth:`stats`; the engine's writer thread
    calls the completion sink; one watcher thread polls foreign-claimed
    cohorts.  One lock (``_lock``) guards all session state; device work
    never runs under it.
    """

    def __init__(self, store_root: str, *,
                 jobs="auto", dispatch_ahead: Optional[int] = None,
                 devices: Optional[int] = None,
                 lease_timeout: float = 60.0,
                 max_retries: int = 1, retry_backoff: float = 0.5,
                 max_queued_s_per_client: float = 600.0,
                 poll_s: float = 1.0, verbose: bool = False,
                 checkpoint_every: Optional[int] = None,
                 flight: bool = False, sentinel: Optional[str] = None):
        self.store = store_lib.SweepStore(store_root)
        # startup hygiene: debris from crashed writers older than one
        # lease cannot belong to a live process (satellite fix — the
        # sweep is also SURFACED via store.health(), not just stderr)
        self.store.gc_tmp(lease_timeout)
        self.costs = store_lib.CostBook(store_root)
        if jobs == "auto":
            jobs = admission_lib.auto_jobs(self.costs)
        if dispatch_ahead is None:
            dispatch_ahead = admission_lib.auto_dispatch_ahead(jobs)
        self.verbose = verbose
        # ONE registry for the whole daemon: /metrics renders it, the
        # engine's counters/histograms write into it, and the session
        # counters mirror into it — one path, no drift
        self.registry = metrics_lib.Registry(namespace="repro_serve")
        self.mesh = shard_lib.sweep_mesh(devices)
        self.engine = CohortEngine(jobs=jobs,
                                   dispatch_ahead=dispatch_ahead,
                                   mesh=self.mesh, verbose=verbose,
                                   registry=self.registry)
        self.board = ClaimBoard(store_root, host_id=os.getpid(),
                                lease_timeout=lease_timeout)
        self.board.start_heartbeat()
        self.admission = admission_lib.AdmissionPolicy(
            max_queued_s_per_client=max_queued_s_per_client)
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        # in-flight telemetry: a flight recorder taps round-level signals
        # out of the engine's blocked cohorts; /live and the
        # rounds-in-flight gauge read it.  Taps exist only at block
        # boundaries, so --flight implies blocked execution.
        if flight and checkpoint_every is None:
            checkpoint_every = 25
        self.checkpoint_every = checkpoint_every
        self.flight = None
        if flight:
            self.flight = flight_lib.install(
                flight_lib.flight_dir_for(store_root),
                predicates=sentinel)
            self.flight.on_tap = self._on_tap
        self.started = time.time()

        self._lock = threading.RLock()
        self._requests: Dict[str, Request] = {}
        self._inflight: Dict[str, _Inflight] = {}
        self._cells_inflight: Dict[str, _Inflight] = {}
        self._counters: Dict[str, int] = {}
        self._rid = itertools.count(1)
        self._closed = False

        self._poll_s = poll_s
        self._register_gauges()
        self._watch_stop = threading.Event()
        self._watcher = threading.Thread(target=self._watch_loop,
                                         name="serve-watch", daemon=True)
        self._watcher.start()

    # ------------------------------------------------------------- helpers
    def _bump(self, name: str, n: int = 1) -> None:
        self._counters[name] = self._counters.get(name, 0) + n
        self.registry.counter(_METRIC_ALIAS.get(name, name)).inc(n)

    def _register_gauges(self) -> None:
        """Point-in-time readings sampled at render time.  Series names
        match the pre-registry flattened /stats names, so dashboards
        built against PR 7 keep working."""
        reg = self.registry
        reg.gauge("uptime_s", "seconds since service start",
                  fn=lambda: time.time() - self.started)
        reg.gauge("requests_known",
                  fn=lambda: len(self._requests))
        reg.gauge("requests_active",
                  fn=lambda: sum(1 for r in self._requests.values()
                                 if not r.done.is_set()))
        reg.gauge("cache_hit_rate", "hit cells / requested cells",
                  fn=self._hit_rate)
        reg.gauge("inflight_total",
                  fn=lambda: len(self._inflight))
        reg.gauge("inflight_waiting",
                  fn=lambda: sum(1 for i in self._inflight.values()
                                 if i.kind == "waiting"))
        reg.gauge("claims_held", fn=lambda: len(self.board.held()))
        reg.gauge("claims_steals", fn=lambda: self.board.steals)
        reg.gauge("engine_jobs", fn=lambda: self.engine.jobs)
        reg.gauge("engine_dispatch_ahead",
                  fn=lambda: self.engine.dispatch_ahead)
        reg.gauge("costs_measured_keys",
                  fn=lambda: len(admission_lib._measured_walls(
                      self.costs)))
        reg.gauge("store_cells", fn=lambda: len(self.store))
        reg.gauge("admission_max_queued_s_per_client",
                  fn=lambda: self.admission.max_queued_s)
        reg.gauge("rounds_in_flight",
                  "rounds not yet flown across running tapped cohorts",
                  fn=lambda: (self.flight.rounds_remaining()
                              if self.flight is not None else 0))

    def _on_tap(self, snap: Dict[str, Any]) -> None:
        """Flight-recorder hook (engine thread): fold each tap's realized
        rate into the per-cohort rounds/sec histogram."""
        rate = snap.get("rounds_per_s")
        if rate is not None:
            self.registry.histogram(
                "cohort_rounds_per_s",
                "realized rounds/sec per flight tap").observe(rate)

    def _hit_rate(self) -> float:
        served = self._counters.get("cells_requested", 0)
        hits = self._counters.get("cells_hit", 0)
        return (hits / served) if served else 0.0

    def metrics_text(self) -> str:
        """Prometheus exposition of the daemon registry and the process
        registry (the /metrics endpoint).  Label-carrying series
        (per-client admission charge, store health notes) are refreshed
        here — everything else is a live counter or a callback gauge."""
        g = self.registry.gauge("admission_queued_s",
                                "queued device-seconds per client")
        g.clear_labeled()
        for client, s in self.admission.queued().items():
            g.set_labeled(s, client=str(client))
        notes = self.registry.gauge("store_note_counts",
                                    "store health incidents by kind")
        notes.clear_labeled()
        for kind, n in self.store.health()["note_counts"].items():
            notes.set_labeled(n, kind=str(kind))
        # then the process's own series (``repro_*``: the worker-sharded
        # engine's build-time gauges)
        return (self.registry.render_prometheus()
                + metrics_lib.process_registry().render_prometheus())

    # -------------------------------------------------------------- submit
    def submit(self, spec: grid_lib.SweepSpec,
               client: str = "default") -> Dict[str, Any]:
        """Register a grid request; returns the immediate plan snapshot.

        Raises :class:`admission_lib.AdmissionRejected` (HTTP 429 at the
        API layer) BEFORE any subscription, claim, or dispatch — a
        rejected request leaves the service exactly as it found it.
        """
        cache_key = grid_lib.spec_cache_key(spec)
        cell_list = grid_lib.cells(spec)
        hashes = [store_lib.cell_hash(c, cache_key) for c in cell_list]
        with self._lock:
            if self._closed:
                raise RuntimeError("service is shutting down")
            # ---- phase 1: classify (read-only) -------------------------
            hit_docs: Dict[str, Dict[str, Any]] = {}
            shared: Dict[str, _Inflight] = {}
            miss_cells, miss_idx = [], []
            with obs_trace.span("session.classify", cat="serve",
                                client=client,
                                cells=len(cell_list)) as sp:
                for i, (cell, h) in enumerate(zip(cell_list, hashes)):
                    if h in self._cells_inflight:
                        shared[h] = self._cells_inflight[h]
                        continue
                    if h in hit_docs:
                        continue                   # duplicate grid cell
                    doc = self.store.get(cell, cache_key)
                    if doc is not None:
                        hit_docs[h] = doc
                    else:
                        miss_cells.append(cell)
                        miss_idx.append(i)
                sp["hits"] = len(hit_docs)
                sp["shared"] = len(shared)
                sp["misses"] = len(miss_cells)
            new_cohorts = grid_lib.cohorts(miss_cells, miss_idx)
            ests = [self.admission.estimate(co, self.costs)
                    for co in new_cohorts]
            # ---- phase 2: admit (can raise; still nothing mutated) -----
            self.admission.admit(client, sum(ests))
            # ---- phase 3: register + dispatch --------------------------
            req = Request(f"r{next(self._rid)}", spec, cell_list, hashes,
                          cache_key, client)
            self._requests[req.id] = req
            self._bump("requests_total")
            self._bump("cells_requested", len(cell_list))
            self._bump("cells_hit", len(hit_docs))
            for h, doc in hit_docs.items():
                req.deliver_hit(h, doc)
            for h, inf in shared.items():
                req.mark_pending(h, "shared")
                if req not in inf.subscribers:
                    inf.subscribers.append(req)
                self._bump("cells_shared")
            to_run = []
            for co, est in zip(new_cohorts, ests):
                sig = grid_lib.cohort_signature(co, cache_key)
                if self.board.try_claim(sig):
                    inf = _Inflight(sig, co, cache_key, kind="scheduled",
                                    client=client, est_s=est)
                    to_run.append(inf)
                    status = "scheduled"
                    self._bump("cells_scheduled", len(co))
                else:
                    # a foreign process holds a live lease: watch the
                    # store instead of computing the cohort twice
                    self.admission.release(client, est)
                    inf = _Inflight(sig, co, cache_key, kind="waiting",
                                    client=client, est_s=0.0)
                    status = "waiting"
                    self._bump("cells_waiting", len(co))
                inf.subscribers.append(req)
                self._inflight[sig] = inf
                for h in inf.hashes:
                    self._cells_inflight[h] = inf
                    req.mark_pending(h, status)
            if to_run:
                self._dispatch(to_run)
            if not req._pending:
                req.done.set()
            obs_trace.event("session.submit", cat="serve",
                            request=req.id, client=client,
                            cells=len(cell_list), hits=len(hit_docs),
                            shared=len(shared),
                            scheduled=sum(len(i.cohort)
                                          for i in to_run))
            obs_trace.flush()   # fully-cached requests never settle
            snap = req.snapshot()
            snap["plan"] = {"hits": len(hit_docs), "shared": len(shared),
                            "scheduled": sum(len(i.cohort) for i in to_run),
                            "waiting": sum(len(i.cohort)
                                           for i in self._inflight.values()
                                           if i.kind == "waiting"
                                           and req in i.subscribers)}
            return snap

    def _dispatch(self, inflights: List[_Inflight]) -> None:
        """Submit claimed cohorts to the engine as one batch (called
        under the lock; the engine only enqueues here)."""
        by_sig = {inf.sig: inf for inf in inflights}
        cache_key = inflights[0].cache_key
        spec = inflights[0].subscribers[0].spec

        def sink(cohort, results):
            sig = grid_lib.cohort_signature(cohort, cache_key)
            for res in results:
                self.store.put(res["cell"], res, cache_key)
            # clear any stale quarantine record BEFORE marking the
            # request done, so "done" implies a fully consistent store
            # (the engine also clears it, but on its own thread timing)
            resilience.QuarantineLog(self.store.root).clear(sig)
            self._settle(sig, "done")

        def on_quarantine(cohort, exc, attempts):
            sig = grid_lib.cohort_signature(cohort, cache_key)
            self._settle(sig, "quarantined",
                         f"{type(exc).__name__}: {exc} "
                         f"({attempts} attempt(s))")

        def on_fatal(exc):
            for sig in list(by_sig):
                self._settle(sig, "failed",
                             f"{type(exc).__name__}: {exc}")

        self.engine.submit(
            [inf.cohort for inf in inflights], sink=sink,
            do_eval=spec.eval, tail=spec.tail, costs=self.costs,
            store_root=self.store.root, cache_key=cache_key,
            checkpoint_every=self.checkpoint_every,
            max_retries=self.max_retries,
            retry_backoff=self.retry_backoff,
            quarantine=True, verbose=self.verbose,
            on_quarantine=on_quarantine, on_fatal=on_fatal)

    # -------------------------------------------------------- completions
    def _settle(self, sig: str, status: str, msg: str = "") -> None:
        """Terminal transition for one in-flight cohort: deliver to every
        subscriber, release claim + admission charge, gc when idle."""
        with self._lock:
            inf = self._inflight.pop(sig, None)
            if inf is None:
                return                    # already settled (e.g. fatal
                                          # after quarantine)
            for h, cell in zip(inf.hashes, inf.cohort.cells):
                self._cells_inflight.pop(h, None)
                if status == "done":
                    # read back through the store: subscribers get the
                    # exact document a one-shot run would serve
                    doc = self.store.get(cell, inf.cache_key)
                    for req in inf.subscribers:
                        if doc is not None:
                            req.deliver(h, doc)
                        else:
                            req.mark_terminal(h, "failed",
                                              "store read-back miss")
                else:
                    for req in inf.subscribers:
                        req.mark_terminal(h, status, msg)
            if inf.kind == "scheduled":
                self.board.release(inf.sig)
                self.admission.release(inf.client, inf.est_s)
            self._bump(f"cohorts_{status}")
            if status != "done":
                self._bump(f"cells_{status}", len(inf.cohort))
            else:
                self._bump("cells_computed", len(inf.cohort))
            obs_trace.event("session.settle", cat="serve", sig=sig,
                            status=status, cells=len(inf.cohort))
            obs_trace.flush()   # request lifecycle over: persist its tail
            if not self._inflight:
                # fully idle: drop empty .runtime debris so the store
                # stays byte-comparable with any clean one-shot run
                grid_lib.runtime_gc(self.store.root)

    # ------------------------------------------------------------- watcher
    def _watch_loop(self) -> None:
        """Poll foreign-claimed cohorts: stream cells in as the foreign
        worker lands them; steal the claim if its lease goes stale."""
        while not self._watch_stop.wait(self._poll_s):
            with self._lock:
                waiting = [inf for inf in self._inflight.values()
                           if inf.kind == "waiting"]
            for inf in waiting:
                self._watch_one(inf)

    def _watch_one(self, inf: _Inflight) -> None:
        landed = []
        for h, cell in zip(inf.hashes, inf.cohort.cells):
            if h not in inf.remaining:
                continue
            doc = self.store.get(cell, inf.cache_key)
            if doc is not None:
                landed.append((h, doc))
        with self._lock:
            if self._inflight.get(inf.sig) is not inf:
                return                    # settled while we polled
            for h, doc in landed:
                inf.remaining.discard(h)
                self._cells_inflight.pop(h, None)
                for req in inf.subscribers:
                    req.deliver(h, doc)
                self._bump("cells_computed")
            if not inf.remaining:
                self._inflight.pop(inf.sig, None)
                self._bump("cohorts_done")
                if not self._inflight:
                    grid_lib.runtime_gc(self.store.root)
                return
        # not finished: did the foreign worker quarantine it?
        failed = resilience.failed_cell_hashes(self.store.root)
        if set(inf.remaining) <= failed:
            self._settle(inf.sig, "quarantined",
                         "quarantined by another worker "
                         "(see <store>/failed/)")
            return
        # or die? a stale lease is stealable — compute it ourselves
        if self.board.try_claim(inf.sig):
            with self._lock:
                if self._inflight.get(inf.sig) is not inf \
                        or not inf.remaining:
                    self.board.release(inf.sig)
                    return
                inf.kind = "scheduled"
                inf.est_s = 0.0           # charge was already released
                self._bump("claims_stolen")
                obs_trace.event("session.steal", cat="serve",
                                sig=inf.sig, cells=len(inf.remaining))
                for req in inf.subscribers:
                    for h in inf.remaining:
                        if req.status.get(h) == "waiting":
                            req.status[h] = "scheduled"
                self._dispatch([inf])

    # ------------------------------------------------------------- queries
    def request_snapshot(self, rid: str,
                         include_results: bool = False
                         ) -> Optional[Dict[str, Any]]:
        with self._lock:
            req = self._requests.get(rid)
            return None if req is None \
                else req.snapshot(include_results)

    def cell(self, h: str) -> Optional[Dict[str, Any]]:
        return self.store.get_by_hash(h)

    def live(self, rid: Optional[str] = None) -> Dict[str, Any]:
        """The /live document: every in-flight cohort (or one request's)
        with its flight snapshot, realized rounds/sec, and an ETA —
        flight-rate-scaled when taps exist, CostBook walls otherwise.

        Raises ``KeyError`` for an unknown ``rid`` (the API layer's 404).
        """
        with self._lock:
            if rid is not None and rid not in self._requests:
                raise KeyError(rid)
            inflights = [
                inf for inf in self._inflight.values()
                if rid is None or any(r.id == rid
                                      for r in inf.subscribers)]
            rows = []
            for inf in inflights:
                snap = (self.flight.snapshot(inf.sig)
                        if self.flight is not None else None)
                eta, source = None, None
                if snap is not None and snap.get("eta_s") is not None:
                    eta, source = snap["eta_s"], "flight"
                else:
                    wall = self.costs.per_cell_wall(
                        grid_lib.cohort_static_hash(inf.cohort))
                    if wall is not None:
                        eta = wall * len(inf.cohort)
                        if snap and snap.get("rounds"):
                            # scale the whole-cohort wall by what's left
                            frac = 1.0 - (snap.get("r_done", 0)
                                          / snap["rounds"])
                            eta *= max(frac, 0.0)
                        source = "costbook"
                rows.append({
                    "sig": inf.sig, "kind": inf.kind,
                    "cells": len(inf.cohort),
                    "requests": sorted(r.id for r in inf.subscribers),
                    "flight": snap, "eta_s": eta, "eta_source": source,
                })
        return {"ts": time.time(),
                "rounds_in_flight": (self.flight.rounds_remaining()
                                     if self.flight is not None else 0),
                "cohorts": rows}

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            counters = dict(self._counters)
            requests = len(self._requests)
            active = sum(1 for r in self._requests.values()
                         if not r.done.is_set())
            inflight = len(self._inflight)
            waiting = sum(1 for i in self._inflight.values()
                          if i.kind == "waiting")
        served = counters.get("cells_requested", 0)
        hits = counters.get("cells_hit", 0)
        walls = admission_lib._measured_walls(self.costs)
        return {
            "uptime_s": time.time() - self.started,
            "requests": {"total": counters.get("requests_total", 0),
                         "known": requests, "active": active},
            "cells": {k[len("cells_"):]: v for k, v in counters.items()
                      if k.startswith("cells_")},
            "cache_hit_rate": (hits / served) if served else None,
            "cohorts": {k[len("cohorts_"):]: v
                        for k, v in counters.items()
                        if k.startswith("cohorts_")},
            "engine": {**self.engine.counters.snapshot(),
                       "jobs": self.engine.jobs,
                       "dispatch_ahead": self.engine.dispatch_ahead,
                       "writer_queue_depth": self.engine.pending()},
            "inflight": {"total": inflight, "waiting": waiting},
            "claims": {"held": len(self.board.held()),
                       "steals": self.board.steals,
                       "stolen_from_foreign":
                           counters.get("claims_stolen", 0)},
            "admission": {"queued_s_by_client": self.admission.queued(),
                          "max_queued_s_per_client":
                              self.admission.max_queued_s},
            "costs": {"measured_keys": len(walls),
                      "median_per_cell_wall_s":
                          (walls[len(walls) // 2] if walls else None)},
            "store": {"cells": len(self.store), **self.store.health()},
        }

    # ------------------------------------------------------------ shutdown
    def close(self) -> None:
        with self._lock:
            self._closed = True
        self._watch_stop.set()
        self._watcher.join(timeout=10.0)
        try:
            self.engine.close()
        finally:
            self.board.stop_heartbeat()
            for sig in self.board.held():
                self.board.release(sig)
            if self.flight is not None:
                self.flight.flush()
