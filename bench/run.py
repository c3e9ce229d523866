#!/usr/bin/env python3
"""The benchmark of the OTA-FL system: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  Everything that
belongs to one configuration, traffic mix or per-layer metric is a file
found by name: ``bench/configs/<config>.json`` (sizes, the system's
settings and the plain reference beside it in ``bench/reference/``),
``bench/traffic/<mix>.json`` (parameters that one general generator in
``bench/generators/<generator>.py`` reads) and ``bench/layer_metrics/<metric>.py``
(a reader of spans, counters or the trace).

A run: check the chip, set up the cell (data from ``--seed``, compiles,
warm-up: ``setup_s``), measure for ``--seconds``, read the peak device
memory, free the system's state, then compare what the window produced
with the plain reference and print the numbers compared beside their
limits.  With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the JAX profiler and the
line carries the per-layer metrics, ``busy_s`` / ``window_s`` and a
``breakdown``.  The last line of standard output is that JSON object.
Exit codes: 0 for a run that printed its line, 1 when JAX finds no TPU or
too few chips, 2 outside a checkout.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache: one fixed directory in the checkout,
# of the benchmark's own, so every run after a cell's first reads its
# programs back
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
# traces are written here, reduced, and deleted before the run ends
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_module(path: str, name: str):
    """Import a benchmark file by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration and its traffic mix, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def layer_reader(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "bench", "layer_metrics",
                                    f"{name}.py"), f"layer_metric_{name}")


def generator_module(traffic: dict, root: str = ROOT):
    name = traffic["generator"]
    return load_module(os.path.join(root, "bench", "generators", f"{name}.py"),
                       f"bench_generator_{name}")


class CompileClock:
    """Backend compiles (persistent-cache reads included) and cache hits,
    from JAX's monitoring events, across all threads."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1

    def snapshot(self):
        with self._lock:
            return {"seconds": self.seconds, "compiles": self.compiles,
                    "hits": self.hits}


class Context:
    """What the generator and the layer readers share in one run."""

    def __init__(self, args, loaded, devices, clock):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.devices = devices
        self.chips = int(self.cell["chips"])
        self.clock = clock
        self.scratch = os.path.join(ROOT, ".bench_scratch",
                                    self.cell["name"])
        self.window = {}        # what the generator measured in the window
        self.reduced = None     # the trace reduction (--trace 1)
        with open(os.path.join(BENCH, "peaks.json")) as f:
            self.peaks = json.load(f)

    def peak(self):
        """The chip's published peaks; an unknown device is an error."""
        kind = self.devices[0].device_kind
        table = self.peaks["devices"]
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r}")
        return table[kind]


def prepare_jax(chips: int):
    """Import JAX with the benchmark's compilation cache; the cell's
    devices, or None (with the reason on stderr) when JAX finds no TPU or
    fewer chips than the cell asks for."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (first device: "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices[:chips]


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no repro package under {src}; run from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = load_cell(json.load(f), args.workload)

    devices = prepare_jax(int(loaded["cell"]["chips"]))
    if devices is None:
        return 1
    ctx = Context(args, loaded, devices, CompileClock())
    shutil.rmtree(ctx.scratch, ignore_errors=True)
    gen = generator_module(loaded["traffic"])
    try:
        return run_cell(ctx, gen, loaded)
    finally:
        shutil.rmtree(ctx.scratch, ignore_errors=True)


def run_cell(ctx: Context, gen, loaded) -> int:
    import jax

    state = gen.setup(ctx)
    t_window = time.time()
    setup_s = t_window - T_START
    ctx.window["compile_before"] = ctx.clock.snapshot()
    if ctx.trace:
        trace_seconds = min(ctx.seconds,
                            float(ctx.traffic.get("trace_seconds",
                                                  ctx.seconds)))
        trace_reduce = load_module(os.path.join(BENCH, "trace_reduce.py"),
                                   "bench_trace_reduce")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(
            TRACE_DIR, profiler_options=trace_reduce.profile_options())
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                answers = gen.window(ctx, state, trace_seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        answers = gen.window(ctx, state, ctx.seconds)
    ctx.window["compile_after"] = ctx.clock.snapshot()
    mem = memory_peak(ctx.devices)
    e2e = gen.end_to_end(ctx, state, answers)
    e2e["setup_s"] = setup_s
    attempted, failed = gen.attempted_failed(ctx, answers)
    gen.release(ctx, state)
    del state

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": ctx.devices[0].platform,
                  "kind": ctx.devices[0].device_kind,
                  "count": len(ctx.devices),
                  "memory_peak_bytes": mem}}
    if ctx.trace:
        ctx.reduced = trace_reduce.reduce_dir(TRACE_DIR,
                                              n_devices=len(ctx.devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        result["device"]["busy_s"] = ctx.reduced["busy_s"]
        result["device"]["window_s"] = ctx.reduced["window_s"]
        result["breakdown"] = ctx.reduced["breakdown"]
        for m in loaded["per_layer"]:
            value = layer_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        for m in loaded["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    checks = gen.check(ctx, answers)
    correct = (failed == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result["correct"] = correct
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
