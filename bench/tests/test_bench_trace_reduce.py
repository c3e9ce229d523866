"""The reduction from a profiler trace to busy time, op time and labelled
idle gaps, on a small hand-made trace with known answers."""

import collections
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce  # noqa: E402

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


def _planes():
    # window 0..1000 ns; device 0 runs a 100..300 and b 250..400 (busy
    # 100..400) and a 700..800 inside a loop 650..850 (busy 650..850);
    # device 1 runs b 0..500 (busy 500).
    # Host: run_spec covers 350..850, dispatch covers 500..600.
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step", 0, 1000)]),
        Line("XLA Ops", [Ev("%a = f32[2] fusion(%p)", 100, 200),
                         Ev("%b = f32[2] custom-call(%q)", 250, 150),
                         Ev("%while.3 = (s32[]) while(%t)", 650, 200),
                         Ev("%a = f32[2] fusion(%p)", 700, 100)])])
    dev1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [Ev("b", 0, 500), Ev("c", 1200, 50)])])
    host = Plane("/host:CPU", [
        Line("python", [Ev(trace_reduce.WINDOW, 0, 1000),
                        Ev("bench.grid.run_spec", 350, 500),
                        Ev("dispatch", 500, 100)])])
    return [dev0, dev1, host, Plane("/device:TPU:0 SparseCore", [])]


def test_busy_ops_and_window():
    r = trace_reduce.reduce_planes(_planes(), n_devices=2)
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0: 300 + 200 = 500 ns; device 1: 500 ns (c is outside);
    # the loop counts as busy but not as an operation of its own
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["ops"] == pytest.approx({"a": 150e-9, "b": 325e-9})


def test_idle_gaps_are_labelled_by_the_host():
    r = trace_reduce.reduce_planes(_planes(), n_devices=2)
    # device 0 idles 0..100, 400..650 and 850..1000
    assert [(k, pytest.approx(v)) for k, v in r["gaps"]] == [
        ("dispatch", 250e-9), ("host idle", 150e-9),
        ("host idle", 100e-9)]
    assert r["breakdown"]["device_ops"][0] == ["b", pytest.approx(325e-9)]


def test_only_the_cells_devices_count():
    r = trace_reduce.reduce_planes(_planes(), n_devices=1)
    assert r["busy_s"] == pytest.approx(500e-9)


def test_kernel_seconds_matches_names_or_reports_nothing():
    r = trace_reduce.reduce_planes(_planes(), n_devices=2)
    assert trace_reduce.kernel_seconds(r, r"^a$") == pytest.approx(150e-9)
    assert trace_reduce.kernel_seconds(r, "ota_round") is None


def test_a_trace_without_the_window_is_refused():
    planes = [p for p in _planes() if p.name != "/host:CPU"]
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes, n_devices=2)


def _reader(name):
    from bench import run as bench_run
    return bench_run.load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "layer_metrics", f"{name}.py"), f"reader_{name}")


class _Ctx:
    def __init__(self, window, ops, busy_s=2.0):
        self.window = window
        self.reduced = {"ops": ops, "busy_s": busy_s, "window_s": 4.0}
        self.devices = [object()]
        self.config = {"U": 100000, "k_bar": 30, "k_spread": 5, "D": 3}

    def peak(self):
        return {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_round_mfu_divides_by_the_device_busy_time():
    from bench.counts import round_linreg_pop
    ctx = _Ctx({"rounds": 4, "elapsed_s": 99.0}, {})
    n = round_linreg_pop.counts(100000, 30.0, 35, 3)
    least = 4 * max(n["flops"] / 197e12, n["bytes"] / 819e9)
    assert _reader("round_mfu.pop").read(ctx) == pytest.approx(
        100 * least / 2.0)


@pytest.mark.parametrize("name,window,ops,want", [
    ("ota_round_us.grid", {"inflota_exp_rounds": 1000},
     {"ota_round.7": 0.05}, 50.0),
    ("ota_shard_tx_us.pop", {"rounds": 4}, {"shard_tx.3": 0.02}, 5000.0),
    ("ota_round_us.grid", {"inflota_exp_rounds": 1000}, {"fusion.1": 1.0},
     None),
])
def test_kernel_time_per_round_or_nothing(name, window, ops, want):
    got = _reader(name).read(_Ctx(window, ops))
    assert got == (None if want is None else pytest.approx(want))
