"""Sweep service tier (ISSUE 7): cache-hit serving, in-flight cohort
dedup, claim-board coordination with foreign workers, admission, the
HTTP API, and daemon crash-resumability.

The service inherits the runtime's load-bearing guarantee: no serving
path may change result BYTES — a daemon-computed store must be
byte-identical to a one-shot serial run of the same grid, and cached
cells must be served with ZERO scheduler dispatches.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from repro.runtime import faults, resilience
from repro.runtime.claims import ClaimBoard
from repro.serve import admission as admission_lib
from repro.serve import api as api_lib
from repro.serve import client as client_lib
from repro.serve import session as session_lib
from repro.sweep import SweepSpec, SweepStore, cells, cohorts, run_spec
from repro.sweep import grid as grid_mod
from repro.sweep.grid import cohort_signature, spec_cache_key
from repro.sweep.store import CostBook

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _float32_mode():
    """Byte-identity compares against subprocess runs (default f32)."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


@pytest.fixture(autouse=True)
def _no_ambient_faults():
    faults.install(faults.parse(""))
    yield
    faults.install(None)


U, K_BAR, ROUNDS = 4, 6, 5

# two cohorts (policy is static), four cells
SPEC = SweepSpec(axes={"seed": (0, 1), "policy": ("inflota", "random")},
                 base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS,
                       "backend": "jnp"})
# one cohort, two cells
SPEC_1CO = SweepSpec(axes={"seed": (0, 1)},
                     base={"U": U, "k_bar": K_BAR, "rounds": ROUNDS,
                           "backend": "jnp"})

_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=os.pathsep.join(
                [os.path.join(os.path.dirname(__file__), "..", "src")]
                + sys.path))


def _store_files(root):
    return {f: open(os.path.join(root, f), "rb").read()
            for f in sorted(os.listdir(root)) if f.endswith(".json")}


def _service(root, **kw):
    kw.setdefault("jobs", 2)
    kw.setdefault("poll_s", 0.1)
    return session_lib.SweepService(str(root), **kw)


def _wait_done(svc, rid, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        snap = svc.request_snapshot(rid)
        if snap["state"] == "done":
            return snap
        time.sleep(0.05)
    raise AssertionError(f"request {rid} never settled: "
                         f"{svc.request_snapshot(rid)}")


# --------------------------------------------------------------- spec wire

def test_spec_doc_roundtrip():
    doc = session_lib.spec_to_doc(SPEC)
    spec2 = session_lib.spec_from_doc(json.loads(json.dumps(doc)))
    key = spec_cache_key(SPEC)
    from repro.sweep.store import cell_hash
    assert [cell_hash(c, key) for c in cells(SPEC)] == \
        [cell_hash(c, spec_cache_key(spec2)) for c in cells(spec2)]


def test_spec_from_doc_rejects_garbage():
    with pytest.raises(ValueError):
        session_lib.spec_from_doc({"no": "axes"})
    with pytest.raises(ValueError):
        session_lib.spec_from_doc([1, 2])
    with pytest.raises(ValueError):        # unknown cell field
        session_lib.spec_from_doc({"axes": {"bogus_field": [1]}})


# -------------------------------------------------------------- auto-tune

def test_auto_jobs_sizing(tmp_path):
    # no measurements: conservative pool, capped by cpus-1
    assert admission_lib.auto_jobs(None, cpu_count=16) == 2
    assert admission_lib.auto_jobs(None, cpu_count=2) == 1
    book = CostBook(str(tmp_path))
    book.record("k1", wall_s=0.01, cells=1)      # tiny: overhead-bound
    assert admission_lib.auto_jobs(book, cpu_count=16) == 2
    book.record("k2", wall_s=50.0, cells=10)     # real work
    book.record("k3", wall_s=40.0, cells=10)
    book._cache = None
    assert admission_lib.auto_jobs(book, cpu_count=16) == 4
    assert admission_lib.auto_jobs(book, cpu_count=3) == 2


def test_auto_dispatch_ahead():
    assert admission_lib.auto_dispatch_ahead(1) == 2
    assert admission_lib.auto_dispatch_ahead(8) == 4


def test_run_spec_jobs_auto(tmp_path):
    d = str(tmp_path / "auto")
    results = run_spec(SPEC, store=SweepStore(d), jobs="auto")
    assert all(r is not None for r in results)
    ref = str(tmp_path / "serial")
    run_spec(SPEC, store=SweepStore(ref))
    assert _store_files(d) == _store_files(ref)


# -------------------------------------------------------------- admission

def test_admission_policy_bounds_per_client():
    pol = admission_lib.AdmissionPolicy(max_queued_s_per_client=50.0,
                                        default_cohort_s=30.0)
    pol.admit("a", 30.0)
    with pytest.raises(admission_lib.AdmissionRejected):
        pol.admit("a", 30.0)              # 60 > 50
    pol.admit("b", 30.0)                  # other clients unaffected
    pol.admit("a", 0.0)                   # zero-cost (pure hits) passes
    pol.release("a", 30.0)
    pol.admit("a", 30.0)                  # drained: admitted again
    assert set(pol.queued()) == {"a", "b"}


# ------------------------------------------------------- serving semantics

def test_cache_hits_never_touch_scheduler(tmp_path):
    d = str(tmp_path / "store")
    run_spec(SPEC, store=SweepStore(d))   # seed every cell
    svc = _service(d)
    try:
        def boom(*a, **kw):
            raise AssertionError("cache-hit request reached the engine")
        svc.engine.submit = boom
        snap = svc.submit(SPEC, client="t")
        assert snap["state"] == "done"
        assert snap["plan"] == {"hits": 4, "shared": 0, "scheduled": 0,
                                "waiting": 0}
        assert snap["counts"] == {"hit": 4}
        full = svc.request_snapshot(snap["id"], include_results=True)
        assert len(full["results"]) == 4
        assert all("metrics" in doc for doc in full["results"].values())
    finally:
        svc.engine.submit = lambda *a, **kw: None
        svc.close()


def test_served_store_byte_identical_and_resubmit_all_hits(tmp_path):
    ref = str(tmp_path / "serial")
    run_spec(SPEC, store=SweepStore(ref))
    d = str(tmp_path / "served")
    svc = _service(d)
    try:
        snap = svc.submit(SPEC, client="t")
        assert snap["plan"]["scheduled"] == 4
        snap = _wait_done(svc, snap["id"])
        assert snap["counts"] == {"done": 4}
        # THE acceptance invariant: a daemon-executed grid's store is
        # byte-identical to the one-shot run, and transient runtime
        # state is gone once idle
        assert _store_files(d) == _store_files(ref)
        assert not os.path.isdir(os.path.join(d, ".runtime"))
        # resubmit: served entirely from cache, ZERO new dispatches
        dispatched = svc.engine.counters.get("cohorts_dispatched")
        snap2 = svc.submit(SPEC, client="t")
        assert snap2["state"] == "done"
        assert snap2["plan"]["hits"] == 4
        assert svc.engine.counters.get("cohorts_dispatched") == dispatched
        stats = svc.stats()
        assert stats["cells"]["hit"] == 4
        assert stats["cells"]["computed"] == 4
        assert stats["cache_hit_rate"] == pytest.approx(0.5)
    finally:
        svc.close()


def test_overlapping_requests_share_inflight_cohorts(tmp_path, monkeypatch):
    """Two concurrent clients with overlapping grids: the shared cells
    are computed ONCE (request B subscribes to A's in-flight cohort)."""
    big = SweepSpec(axes={"seed": (0, 1, 2, 3)}, base=SPEC_1CO.base)
    gate = threading.Event()
    started = threading.Event()
    calls = []
    orig = grid_mod.prepare_cohort

    def gated(cohort, **kw):
        calls.append(sorted(cohort.indices))
        started.set()
        assert gate.wait(timeout=60), "dispatch gate never released"
        return orig(cohort, **kw)

    monkeypatch.setattr(grid_mod, "prepare_cohort", gated)
    svc = _service(str(tmp_path / "store"))
    try:
        snap_a = svc.submit(SPEC_1CO, client="a")      # seeds 0,1
        assert snap_a["plan"]["scheduled"] == 2
        assert started.wait(timeout=60)
        snap_b = svc.submit(big, client="b")           # seeds 0..3
        # b's overlap rides a's in-flight cohort; only seeds 2,3 are new
        assert snap_b["plan"]["shared"] == 2
        assert snap_b["plan"]["scheduled"] == 2
        gate.set()
        done_a = _wait_done(svc, snap_a["id"])
        done_b = _wait_done(svc, snap_b["id"])
        assert done_a["counts"] == {"done": 2}
        assert done_b["counts"] == {"done": 4}
        # the overlapping cohort was prepared exactly once, the new one
        # exactly once — no duplicated device work
        assert len(calls) == 2
        assert svc.stats()["cells"]["shared"] == 2
    finally:
        gate.set()
        svc.close()
    # shared delivery must serve the same bytes a direct run would
    ref = str(tmp_path / "ref")
    run_spec(big, store=SweepStore(ref))
    assert _store_files(str(tmp_path / "store")) == _store_files(ref)


def test_foreign_claim_watched_and_streamed(tmp_path):
    """A cohort claimed by another PROCESS is not recomputed: the
    service watches the store and streams cells as they land."""
    d = str(tmp_path / "store")
    key = spec_cache_key(SPEC_1CO)
    sig = cohort_signature(cohorts(cells(SPEC_1CO))[0], key)
    foreign = ClaimBoard(d, host_id=999, lease_timeout=60.0)
    assert foreign.try_claim(sig)
    svc = _service(d)
    try:
        snap = svc.submit(SPEC_1CO, client="t")
        assert snap["plan"]["waiting"] == 2
        assert snap["plan"]["scheduled"] == 0
        # the foreign worker computes and lands results in the store
        run_spec(SPEC_1CO, store=SweepStore(str(tmp_path / "foreign")))
        SweepStore(d).merge(SweepStore(str(tmp_path / "foreign")))
        snap = _wait_done(svc, snap["id"], timeout=30)
        assert snap["counts"] == {"done": 2}
        assert svc.engine.counters.get("cohorts_dispatched") == 0
    finally:
        foreign.release(sig)
        svc.close()


def test_stale_foreign_claim_stolen(tmp_path):
    """A foreign claim whose lease went stale (dead worker) is stolen
    and the cohort computed locally."""
    d = str(tmp_path / "store")
    key = spec_cache_key(SPEC_1CO)
    sig = cohort_signature(cohorts(cells(SPEC_1CO))[0], key)
    foreign = ClaimBoard(d, host_id=999, lease_timeout=0.5)
    assert foreign.try_claim(sig)
    svc = _service(d, lease_timeout=0.5, poll_s=0.1)
    try:
        snap = svc.submit(SPEC_1CO, client="t")
        assert snap["plan"]["waiting"] == 2
        # the foreign worker dies: its claim stops heartbeating and the
        # lease goes stale (back-dated mtime = no touch for 30s)
        p = os.path.join(foreign.dir, f"{sig}.json")
        os.utime(p, (time.time() - 30, time.time() - 30))
        snap = _wait_done(svc, snap["id"])
        assert snap["counts"] == {"done": 2}
        stats = svc.stats()
        assert stats["claims"]["stolen_from_foreign"] >= 1
        assert svc.board.steals >= 1
    finally:
        svc.close()
    ref = str(tmp_path / "ref")
    run_spec(SPEC_1CO, store=SweepStore(ref))
    assert _store_files(d) == _store_files(ref)


def test_quarantine_streams_and_heals(tmp_path):
    d = str(tmp_path / "store")
    faults.install(faults.parse("fail_cohort:1"))
    svc = _service(d, max_retries=0)
    try:
        snap = svc.submit(SPEC_1CO, client="t")
        snap = _wait_done(svc, snap["id"])
        assert snap["counts"] == {"quarantined": 2}
        assert len(snap["quarantined"]) == 2
        assert resilience.failed_records(d)
        assert svc.stats()["cells"]["quarantined"] == 2
        # heal: clear the fault, resubmit — the cells are store misses,
        # recompute succeeds and clears the quarantine record
        faults.install(faults.parse(""))
        snap2 = svc.submit(SPEC_1CO, client="t")
        snap2 = _wait_done(svc, snap2["id"])
        assert snap2["counts"] == {"done": 2}
        assert not resilience.failed_records(d)
    finally:
        svc.close()


def test_admission_rejected_leaves_no_residue(tmp_path):
    d = str(tmp_path / "store")
    svc = _service(d, max_queued_s_per_client=1.0)   # < default 30s est
    try:
        with pytest.raises(admission_lib.AdmissionRejected):
            svc.submit(SPEC_1CO, client="greedy")
        stats = svc.stats()
        assert stats["requests"]["total"] == 0
        assert not stats["admission"]["queued_s_by_client"]
        assert svc.engine.counters.get("cohorts_dispatched") == 0
        assert svc.board.held() == []
    finally:
        svc.close()
    # pure cache hits are zero-cost and pass the same bound
    run_spec(SPEC_1CO, store=SweepStore(d))
    svc = _service(d, max_queued_s_per_client=1.0)
    try:
        snap = svc.submit(SPEC_1CO, client="greedy")
        assert snap["state"] == "done" and snap["plan"]["hits"] == 2
    finally:
        svc.close()


def test_store_health_surfaces_corrupt_entries(tmp_path):
    d = str(tmp_path / "store")
    run_spec(SPEC_1CO, store=SweepStore(d))
    victim = sorted(f for f in os.listdir(d) if f.endswith(".json"))[0]
    with open(os.path.join(d, victim), "w") as f:
        f.write('{"truncated')
    svc = _service(d)
    try:
        snap = svc.submit(SPEC_1CO, client="t")
        # the corrupt cell reads as a miss and is recomputed; its intact
        # sibling is served from cache
        assert snap["plan"] == {"hits": 1, "shared": 0, "scheduled": 1,
                                "waiting": 0}
        snap = _wait_done(svc, snap["id"])
        assert snap["counts"] == {"hit": 1, "done": 1}
        health = svc.stats()["store"]
        assert health["note_counts"].get("corrupt_entry", 0) >= 1
        assert any("corrupt entry" in n for n in health["notes"])
    finally:
        svc.close()
    ref = str(tmp_path / "ref")
    run_spec(SPEC_1CO, store=SweepStore(ref))
    assert _store_files(d) == _store_files(ref)  # healed byte-identical


# ---------------------------------------------------------------- HTTP API

def _get(base, path):
    with urllib.request.urlopen(f"{base}{path}", timeout=30) as r:
        return json.loads(r.read())


def test_http_api_end_to_end(tmp_path):
    d = str(tmp_path / "store")
    svc = _service(d)
    server = api_lib.make_server(svc, "127.0.0.1", 0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    host, port = server.server_address
    base = f"http://{host}:{port}"
    try:
        assert _get(base, "/healthz") == {"ok": True}
        # client helper: submit + poll to completion, grid-order results
        results, snap = client_lib.submit_and_wait(
            f"{host}:{port}", SPEC_1CO, client="t", poll_s=0.1)
        assert snap["state"] == "done" and len(results) == 2
        assert all("metrics" in r for r in results)
        # /cell/<hash> serves the stored document
        h = snap["cells"][0]["hash"]
        doc = _get(base, f"/cell/{h}")
        assert doc == results[0]
        # /stats JSON + prometheus text
        stats = _get(base, "/stats")
        assert stats["cells"]["computed"] == 2
        assert stats["engine"]["cohorts_completed"] == 1
        req = urllib.request.Request(f"{base}/metrics")
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        assert "repro_serve_cells_computed 2" in text
        assert "# TYPE repro_serve_cache_hit_rate gauge" in text
        # errors: bad spec 400, unknown id 404, unknown route 404
        for path, code in (("/sweep/nope", 404), ("/cell/zz", 404),
                           ("/bogus", 404)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base, path)
            assert ei.value.code == code
        body = json.dumps({"spec": {"axes": {"bogus": [1]}}}).encode()
        post = urllib.request.Request(
            f"{base}/sweep", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(post, timeout=30)
        assert ei.value.code == 400
    finally:
        server.shutdown()
        server.server_close()
        svc.close()


def test_http_admission_is_429(tmp_path):
    svc = _service(str(tmp_path / "store"), max_queued_s_per_client=1.0)
    server = api_lib.make_server(svc, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address
    try:
        with pytest.raises(client_lib.ServiceError) as ei:
            client_lib.submit_and_wait(f"{host}:{port}", SPEC_1CO)
        assert ei.value.status == 429
    finally:
        server.shutdown()
        server.server_close()
        svc.close()


def test_cli_submit_rejects_local_only_flags():
    from repro.sweep import cli
    with pytest.raises(SystemExit):
        cli.main(["--submit", "x:1", "--store", "s",
                  "--axis", "seed=0:2"])
    with pytest.raises(SystemExit):
        cli.main(["--submit", "x:1", "--resume", "--axis", "seed=0:2"])
    with pytest.raises(SystemExit):
        cli.main(["--jobs", "fast", "--axis", "seed=0:2"])


def test_cli_submit_initializes_no_jax_backend(tmp_path):
    """``--submit`` is a pure HTTP client: a process that runs it can
    still hand the chip to another process, because it never starts a
    JAX backend."""
    svc = _service(str(tmp_path / "store"))
    server = api_lib.make_server(svc, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address
    prog = (
        "from repro.sweep import cli\n"
        f"rc = cli.main(['--submit', '{host}:{port}', '--task', 'linreg',"
        f" '--U', '{U}', '--k-bar', '{K_BAR}', '--rounds', '{ROUNDS}',"
        " '--backend', 'jnp', '--axis', 'seed=0:2', '-q'])\n"
        "from jax._src import xla_bridge\n"
        "assert rc == 0, rc\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
        "print('NO-BACKEND')\n")
    try:
        out = subprocess.run([sys.executable, "-c", prog], env=_ENV,
                             capture_output=True, text=True, timeout=300)
    finally:
        server.shutdown()
        server.server_close()
        svc.close()
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("seed,metric,value")   # the served CSV
    assert "NO-BACKEND" in out.stdout


# ----------------------------------------------------------- daemon chaos

def test_killed_daemon_leaves_store_resumable(tmp_path):
    """Hard-kill the daemon mid-sweep (injected power cut at plan cohort
    2); the store must be resumable: a follow-up one-shot run completes
    the grid byte-identical to an uninterrupted reference."""
    d = str(tmp_path / "store")
    env = dict(_ENV, REPRO_FAULTS="kill_at_cohort:2!")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--store", d,
         "--listen", "127.0.0.1:0", "--jobs", "1", "-q"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("listening on "), line
        addr = line.split()[-1]
        try:
            # SPEC has two cohorts: dispatching the second one trips the
            # power cut, so the daemon dies with the request in flight
            client_lib.submit_and_wait(addr, SPEC, poll_s=0.2,
                                       timeout_s=120)
        except client_lib.ServiceError:
            pass                         # daemon died mid-conversation
        rc = proc.wait(timeout=120)
        assert rc == 43, f"daemon should die by injected fault, got {rc}"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # the healing run: startup gc sweeps tmp debris, cached cells hit,
    # missing cells recompute
    results = run_spec(SPEC, store=SweepStore(d))
    assert all(r is not None for r in results)
    ref = str(tmp_path / "ref")
    run_spec(SPEC, store=SweepStore(ref))
    assert _store_files(d) == _store_files(ref)
