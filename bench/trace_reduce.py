"""From a JAX profiler trace to the benchmark's device numbers.

``reduce_dir(trace_dir)`` reads the ``*.xplane.pb`` the profiler wrote
(``jax.profiler.ProfileData``) and returns:

  busy_s     the union of the intervals in which an operation ran on a
             device (``XLA Ops`` line), averaged over the devices;
  window_s   the length of the harness's ``bench.window`` annotation;
  ops        {operation name: device seconds, averaged over the devices},
             named by the HLO instruction (``fusion.12``, ``ota_round.7``);
             control flow (``while``, ``conditional``, ``call``) is left
             out, since the operations it runs are counted themselves;
  gaps       the longest idle gaps of device 0 inside the window, each
             with what the host was doing at its middle;
  breakdown  the ten operations that took most time and the ten longest
             idle gaps, as the result line carries them.

A host span is any event of the host plane (``jax.profiler.TraceAnnotation``
spans of the harness, JAX's own dispatch and compile events); a gap is
labelled by the shortest host span that covers its middle, which is the
most specific thing the host was doing.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTROL = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def profile_options():
    """Profiler options of a traced run: host annotations, no Python
    function tracer (it would record every call of the host path)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _merge(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def reduce_planes(planes, *, n_devices: int, top: int = 10) -> dict:
    """The reduction of already-loaded planes (see the module doc).

    ``planes`` is a sequence of objects with ``name`` and ``lines``; each
    line has ``name`` and ``events``, each event ``name``, ``start_ns`` and
    ``duration_ns``: what ``jax.profiler.ProfileData`` gives.
    """
    window = None
    host = []
    devices = {}
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m is not None:
            if int(m.group(1)) >= n_devices:
                continue
            ops = [ev for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            devices[int(m.group(1))] = [
                (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW:
                        window = span
                    else:
                        host.append(span)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = window[1], window[2]
    busy, ops = 0.0, {}
    for events in devices.values():
        merged = _clip(_merge([(s, e) for _, s, e in events]), lo, hi)
        busy += sum(e - s for s, e in merged) / 1e9
        for name, s, e in events:
            if e > lo and s < hi and name.split(".")[0] not in _CONTROL:
                ops[name] = ops.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    n = len(devices)
    ops = {k: v / n for k, v in ops.items()}
    first = devices[min(devices)]
    merged = _clip(_merge([(s, e) for _, s, e in first]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    spans = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s), key=lambda g: g[0] - g[1])
    gaps = [(_label(host, (s + e) / 2), (e - s) / 1e9)
            for s, e in spans[:top]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / n, "window_s": (hi - lo) / 1e9, "ops": ops,
            "gaps": gaps,
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in gaps]}}


def _label(host, t):
    """The shortest host span covering time ``t``; "host idle" if none."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best is not None else "host idle"


def reduce_dir(trace_dir: str, *, n_devices: int, top: int = 10) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return reduce_planes(ProfileData.from_file(paths[-1]).planes,
                         n_devices=n_devices, top=top)


def kernel_seconds(reduced: dict, pattern: str) -> float | None:
    """Device seconds of the operations whose name matches ``pattern``;
    None when none ran (a reader then reports nothing)."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced["ops"].items() if rx.search(k)]
    return sum(hits) if hits else None
