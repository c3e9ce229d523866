"""The readers of the system's own spans and counters
(``bench/layer_metrics/*``), on a fabricated capture of
``repro.obs.trace.captured()``."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from repro.obs import trace  # noqa: E402


def _reader(name):
    from bench import run as bench_run
    return bench_run.load_module(os.path.join(
        ROOT, "bench", "layer_metrics", f"{name}.py"), f"reader_{name}")


def _span(name, dur_us, **args):
    return {"name": name, "cat": "runtime", "ph": "X", "ts": 0,
            "dur": dur_us, "args": args}


def _event(name, **args):
    return {"name": name, "cat": "sweep", "ph": "i", "s": "t", "ts": 0,
            "args": args}


# two cohorts' spans of each phase, an unrelated span, and three traces
CAPTURE = [
    _span("cohort.prepare", 40_000, cohort=0),
    _span("cohort.prepare", 60_000, cohort=1),
    _span("cohort.dispatch", 1_500_000, cohort=0),
    _span("cohort.dispatch", 2_500_000, cohort=1),
    _span("cohort.wait", 1_000, cohort=0, queued_ms=10.0),
    _span("cohort.wait", 3_000, cohort=1, queued_ms=30.0),
    _span("store.put", 25_000),
    _event("cohort.trace", static="a"), _event("cohort.trace", static="a"),
    _event("cohort.trace", static="b"),
]

READERS = [
    ("prepare_ms.grid", 50.0), ("prepare_ms.serve", 50.0),
    ("dispatch_ms.grid", 2000.0), ("dispatch_ms.serve", 2000.0),
    ("cohort_wait_ms.serve", 22.0), ("traces_per_grid.grid", 1.5),
]


def _ctx():
    return types.SimpleNamespace(window={"grids": 2})


@pytest.mark.parametrize("name,want", READERS)
def test_reader_on_a_capture(monkeypatch, name, want):
    monkeypatch.setattr(trace, "captured", lambda: list(CAPTURE))
    assert _reader(name).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in READERS])
def test_reader_on_an_empty_capture(monkeypatch, name):
    monkeypatch.setattr(trace, "captured", lambda: [])
    assert _reader(name).read(_ctx()) is None


@pytest.mark.parametrize("name", [n for n, _ in READERS])
def test_reader_without_a_capture_api(monkeypatch, name):
    """A program whose tracer has no ``captured`` reads as nothing."""
    monkeypatch.delattr(trace, "captured")
    assert _reader(name).read(_ctx()) is None
