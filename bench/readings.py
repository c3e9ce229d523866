#!/usr/bin/env python3
"""The readings that each limit of ``correct`` is set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3

In one process (set-up once where the generator can be given a new seed):
for each of ``--seeds`` seeds, a short
window of the cell's own traffic at its own sizes and the cell's
comparison with the plain reference (the sound readings); then, for each
of ``--control-seeds`` seeds, the same comparison with the reference in
the system's place, computed in the next lower precision (bfloat16 in
place of float32: the control, which must fail).  Prints one JSON line
per reading and a summary: per compared number the largest sound
reading and the smallest control reading.  The benchmark's own runs do
not run this; ``PERF.md`` records what it printed.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 17)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench import run as bench_run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = bench_run.load_cell(json.load(f), args.workload)
    devices = bench_run.prepare_jax(int(loaded["cell"]["chips"]))
    if devices is None:
        return 1
    gen = bench_run.generator_module(loaded["traffic"])
    sound, control = {}, {}
    state = None
    reuse = hasattr(gen, "reseed")
    for i in range(args.seeds + args.control_seeds):
        seed = args.first_seed + 7919 * i
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = bench_run.Context(ns, loaded, devices,
                                bench_run.CompileClock())
        shutil.rmtree(ctx.scratch, ignore_errors=True)
        if state is None:
            state = gen.setup(ctx)
        else:
            gen.reseed(ctx, state)
        answers = gen.window(ctx, state, args.seconds)
        is_control = i >= args.seeds
        if is_control:
            answers = gen.control_answers(ctx, state, answers)
        if not reuse:
            gen.release(ctx, state)
            state = None
        checks = gen.check(ctx, answers)
        kind = "control" if is_control else "sound"
        print(json.dumps({"kind": kind, "seed": seed,
                          "checks": {k: v["value"]
                                     for k, v in checks.items()}}),
              flush=True)
        into = control if is_control else sound
        for k, v in checks.items():
            into.setdefault(k, []).append(v["value"])
    summary = {k: {"sound_max": max(sound[k]),
                   "control_min": min(control.get(k, [float("nan")])),
                   "limit": loaded["config"]["limits"].get(k)}
               for k in sound}
    print(json.dumps({"summary": summary,
                      "wall_s": time.time() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
