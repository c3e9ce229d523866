"""Observability layer: tracing, metrics, logs, report.

The load-bearing guarantee: telemetry is a pure OBSERVER.  Installing
the trace recorder must never change result bytes — everything it
writes lands under ``<store>/meta/``, which byte-identity comparisons
exclude.  The registry is the single source for every metrics surface,
so its JSON snapshot and its Prometheus text must always agree.
"""

import glob
import io
import json
import os
import threading

import jax
import pytest

from repro.obs import logs, metrics, report, trace
from repro.obs.trace import TraceRecorder
from repro.sweep import SweepSpec, SweepStore, run_spec
from repro.sweep.grid import (cells, cohort_static_hash, cohorts,
                              prepare_cohort)

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _obs_clean(fresh_cohort_fns):
    """Trace install and log mode are process-global: never leak them
    into other tests (or between tests here).  Nor the cohort program
    cache (``fresh_cohort_fns``)."""
    yield
    trace.uninstall()
    trace.clear_captured()
    logs.configure(json_mode=False, stream=None)


U, K_BAR, ROUNDS = 4, 6, 3


# ----------------------------------------------------------------- recorder

def test_recorder_thread_safety(tmp_path):
    rec = TraceRecorder(str(tmp_path), flush_every=8)
    threads, per = 8, 50

    def work(tid):
        for i in range(per):
            with rec.span("work", cat="test", tid=tid, i=i):
                pass
            rec.event("tick", cat="test", tid=tid, i=i)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    rec.close()

    evs = trace.load_events(str(tmp_path))
    assert len(evs) == threads * per * 2        # every record survived
    assert all(e["name"] in ("work", "tick") for e in evs)
    # spans carry integer microsecond ts + dur; events are instants
    for e in evs:
        assert isinstance(e["ts"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        else:
            assert e["ph"] == "i" and e["s"] == "t"


def test_recorder_span_records_error(tmp_path):
    rec = TraceRecorder(str(tmp_path), flush_every=1)
    with pytest.raises(ValueError):
        with rec.span("boom", cat="test"):
            raise ValueError("no")
    rec.close()
    (ev,) = trace.load_events(str(tmp_path))
    assert ev["args"]["error"] == "ValueError"


def test_recorder_unique_files_per_life(tmp_path):
    a = TraceRecorder(str(tmp_path))
    b = TraceRecorder(str(tmp_path))        # same pid, same dir
    assert a.path != b.path
    a.close(), b.close()


def test_module_api_noop_when_uninstalled(tmp_path):
    trace.uninstall()
    assert not trace.enabled()
    trace.event("ignored")                  # must not raise
    with trace.span("ignored") as args:
        args["k"] = 1                       # mutable dict even when off
    trace.flush()
    assert os.listdir(str(tmp_path)) == []


def test_install_from_env(tmp_path, monkeypatch):
    d = str(tmp_path / "t")
    monkeypatch.setenv(trace.ENV_VAR, d)
    assert trace.install_from_env() is not None
    assert trace.enabled()
    trace.event("hello", cat="test")
    trace.uninstall()
    assert [e["name"] for e in trace.load_events(d)] == ["hello"]


# --------------------------------------------------------- profiler bridge

def _host_events(trace_dir, names):
    """{name: [stats dict, ...]} of the host-plane events in the newest
    ``.xplane.pb`` under ``trace_dir``."""
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_span_in_a_profiler_capture_reaches_xplane_and_capture(tmp_path):
    trace.uninstall()
    trace.clear_captured()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("cohort.prepare", cat="test", cells=3) as args:
            args["late"] = 7                # attached mid-span
        trace.event("cohort.trace", cat="test", static="abc")
    finally:
        jax.profiler.stop_trace()
    with trace.span("after.stop", cat="test"):
        pass                                # no capture, no recorder

    got = _host_events(tmp_path, {"cohort.prepare", "cohort.trace"})
    assert got["cohort.prepare"] == [{"cells": 3, "late": 7}]
    assert got["cohort.trace"] == [{"static": "abc"}]
    span, event = trace.captured()
    assert (span["name"], span["ph"], span["args"]) == (
        "cohort.prepare", "X", {"cells": 3, "late": 7})
    assert isinstance(span["dur"], int) and span["dur"] >= 0
    assert (event["name"], event["ph"], event["args"]) == (
        "cohort.trace", "i", {"static": "abc"})


def test_untraced_span_is_the_shared_null_span():
    trace.uninstall()
    trace.clear_captured()
    first = trace.span("a", cells=1)
    assert first is trace.span("b") is trace._NULL_SPAN
    with first as args:
        args["k"] = 1
    trace.event("c")
    assert trace.captured() == []


def _names(trace_dir, name):
    return [e for e in trace.load_events(trace_dir) if e["name"] == name]


def test_cohort_trace_fires_once_per_trace(tmp_path):
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"task": "linreg", "U": U, "k_bar": K_BAR,
                           "rounds": ROUNDS})
    (co,) = cohorts(cells(spec))
    prep = prepare_cohort(co, do_eval=False)
    d = str(tmp_path / "t")
    trace.install(d)
    fn = jax.jit(jax.vmap(prep.run_one))
    jax.block_until_ready(fn(prep.batch))
    jax.block_until_ready(fn(prep.batch))         # a cache hit: no trace
    jax.block_until_ready(jax.jit(jax.vmap(prep.run_one))(prep.batch))
    trace.uninstall()
    evs = _names(d, "cohort.trace")
    assert len(evs) == 2                          # two jitted functions
    assert {e["args"]["static"] for e in evs} == {cohort_static_hash(co)}


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_serial_run_spec_traces_each_cohort_phase(tmp_path):
    spec = SweepSpec(axes={"policy": ("inflota", "random"), "seed": (0, 1)},
                     base={"task": "linreg", "U": U, "k_bar": K_BAR,
                           "rounds": ROUNDS})
    d = str(tmp_path / "t")
    trace.install(d)
    run_spec(spec)
    trace.uninstall()
    runs = _names(d, "cohort.run")
    assert sorted(r["args"]["cohort"] for r in runs) == [0, 1]
    for phase in ("cohort.prepare", "cohort.dispatch", "cohort.resolve"):
        spans = _names(d, phase)
        assert sorted(s["args"]["cohort"] for s in spans) == [0, 1]
        for s in spans:
            (run,) = [r for r in runs
                      if r["args"]["cohort"] == s["args"]["cohort"]]
            assert _inside(s, run)
    assert len(_names(d, "cohort.trace")) == 2


def test_engine_records_cohort_wait(tmp_path):
    spec = SweepSpec(axes={"policy": ("inflota", "random"), "seed": (0, 1)},
                     base={"task": "linreg", "U": U, "k_bar": K_BAR,
                           "rounds": ROUNDS})
    d = str(tmp_path / "t")
    trace.install(d)
    run_spec(spec, jobs=2, dispatch_ahead=0)
    trace.uninstall()
    waits = _names(d, "cohort.wait")
    assert sorted(w["args"]["cohort"] for w in waits) == [0, 1]
    for w in waits:
        assert w["args"]["queued_ms"] >= 0.0 and w["dur"] >= 0
    prepares = _names(d, "cohort.prepare")
    # each cohort waits before its preparation starts (a millisecond of
    # room: ts is the wall clock, dur the performance counter)
    for w in waits:
        (p,) = [p for p in prepares
                if p["args"]["cohort"] == w["args"]["cohort"]]
        assert w["ts"] + w["dur"] <= p["ts"] + 1000


# ------------------------------------------------------------ chrome export

def test_export_chrome_schema(tmp_path):
    rec = TraceRecorder(str(tmp_path), flush_every=1)
    with rec.span("outer", cat="test"):
        rec.event("mark", cat="test", k=1)
    rec.close()

    doc = trace.export_chrome(str(tmp_path))
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    # timestamps re-based to the earliest event
    assert min(e["ts"] for e in evs) == 0
    for e in evs:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i")
    # the whole document round-trips as JSON (Perfetto loads it)
    json.loads(json.dumps(doc))


# ----------------------------------------------------------------- registry

def _parse_prometheus(text):
    """{series-with-labels: float} from exposition text."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        out[name] = float(val.replace("+Inf", "inf"))
    return out


def test_registry_snapshot_matches_prometheus():
    r = metrics.Registry(namespace="repro_test")
    r.counter("jobs_done").inc(3)
    r.gauge("depth").set(2.5)
    r.gauge("temp", fn=lambda: 7.0)
    g = r.gauge("queued_s")
    g.set_labeled(1.5, client="a")
    g.set_labeled(0.25, client="b")
    h = r.histogram("wall_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)

    snap = r.snapshot()
    prom = _parse_prometheus(r.render_prometheus())

    assert snap["jobs_done"] == 3
    assert prom["repro_test_jobs_done"] == 3.0
    assert snap["depth"] == 2.5 == prom["repro_test_depth"]
    assert snap["temp"] == 7 == prom["repro_test_temp"]
    assert snap["queued_s"]["labeled"]['{client="a"}'] == 1.5
    assert prom['repro_test_queued_s{client="a"}'] == 1.5
    assert prom['repro_test_queued_s{client="b"}'] == 0.25
    assert snap["wall_s"]["count"] == 3 == prom["repro_test_wall_s_count"]
    assert snap["wall_s"]["sum"] == pytest.approx(99.55)
    # cumulative buckets agree between surfaces
    assert snap["wall_s"]["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}
    assert prom['repro_test_wall_s_bucket{le="0.1"}'] == 1.0
    assert prom['repro_test_wall_s_bucket{le="1"}'] == 2.0
    assert prom['repro_test_wall_s_bucket{le="+Inf"}'] == 3.0


def test_registry_get_or_create_and_kind_mismatch():
    r = metrics.Registry()
    c = r.counter("n")
    assert r.counter("n") is c
    with pytest.raises(TypeError):
        r.gauge("n")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_callback_failure_reads_zero():
    r = metrics.Registry()
    g = r.gauge("bad", fn=lambda: 1 / 0)
    assert g.get() == 0.0
    assert "bad 0" in r.render_prometheus()


def test_registry_dump(tmp_path):
    r = metrics.Registry(namespace="repro_sweep")
    r.counter("cells_computed").inc(4)
    p = str(tmp_path / "m.json")
    r.dump(p)
    doc = json.load(open(p))
    assert doc["namespace"] == "repro_sweep"
    assert doc["metrics"]["cells_computed"] == 4


# --------------------------------------------------------------------- logs

def test_logs_plain_mode_is_byte_stable():
    buf = io.StringIO()
    logs.configure(json_mode=False, stream=buf)
    logs.emit("serve", "started", plain="store=s jobs=2", extra=1)
    logs.emit("serve", "hidden", plain=None)     # JSON-only record
    logs.raw("listening on 127.0.0.1:8477")
    assert buf.getvalue() == ("# serve: store=s jobs=2\n"
                              "listening on 127.0.0.1:8477\n")


def test_logs_json_mode_one_object_per_line():
    buf = io.StringIO()
    logs.configure(json_mode=True, stream=buf)
    logs.emit("serve", "started", plain="store=s", jobs=2)
    logs.emit("serve", "hidden", plain=None, level="debug")
    logs.raw("listening on 127.0.0.1:8477")
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert len(recs) == 3
    for rec in recs:
        assert {"ts", "level", "component", "event"} <= set(rec)
    assert recs[0]["event"] == "started" and recs[0]["jobs"] == 2
    assert recs[1]["level"] == "debug"
    assert recs[2]["event"] == "raw"
    assert "listening on" in recs[2]["message"]


# ------------------------------------------------- tracing is a pure observer

def _store_files(root):
    return {f: open(os.path.join(root, f), "rb").read()
            for f in sorted(os.listdir(root)) if f.endswith(".json")}


def test_traced_sweep_is_byte_identical(tmp_path):
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"task": "linreg", "U": U, "k_bar": K_BAR,
                           "rounds": ROUNDS})

    plain_root = str(tmp_path / "plain")
    run_spec(spec, store=SweepStore(plain_root), verbose=False)

    traced_root = str(tmp_path / "traced")
    trace.install(trace.trace_dir_for(traced_root))
    try:
        run_spec(spec, store=SweepStore(traced_root), verbose=False)
    finally:
        trace.uninstall()

    # telemetry landed, and ONLY under meta/
    evs = trace.load_events(trace.trace_dir_for(traced_root))
    assert {e["name"] for e in evs} >= {"sweep.submit", "store.put",
                                        "cohort.run"}
    assert _store_files(plain_root) == _store_files(traced_root)


def test_report_renders_and_export(tmp_path):
    root = str(tmp_path / "store")
    spec = SweepSpec(axes={"seed": (0, 1)},
                     base={"task": "linreg", "U": U, "k_bar": K_BAR,
                           "rounds": ROUNDS})
    trace.install(trace.trace_dir_for(root))
    try:
        run_spec(spec, store=SweepStore(root), verbose=False)
    finally:
        trace.uninstall()

    text = report.render(root)
    assert "per-cell OTA telemetry" in text
    assert "seed=0" in text and "seed=1" in text
    assert "trace (" in text
    rows = report.ota_rows(report.load_cells(root))
    assert len(rows) == 2
    for row in rows:
        # realized contraction factor respects the Lemma-1 floor 1-mu/L
        assert row["a_mean"] >= row["a_floor"] - 1e-6
        assert row["gap_bound"] > 0

    doc = trace.export_chrome(trace.trace_dir_for(root))
    assert any(e["name"] == "cohort.run" for e in doc["traceEvents"])


def test_costbook_rows_flag_mispredict(tmp_path):
    root = str(tmp_path / "store")
    SweepStore(root)                        # create the root
    from repro.sweep.store import CostBook
    costs = CostBook(root)
    costs.record("k" * 16, wall_s=10.0, cells=2, predicted_s=1.0)
    costs.record("m" * 16, wall_s=1.0, cells=1, predicted_s=0.9)
    rows = report.costbook_rows(root)
    by_key = {r["key"]: r for r in rows}
    assert by_key["k" * 16]["mispredicted"] is True
    assert by_key["m" * 16]["mispredicted"] is False
