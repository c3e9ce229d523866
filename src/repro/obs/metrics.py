"""Typed metrics registry: counters, gauges, histograms, one render path.

A :class:`Registry` owns every metric a process reports.  The daemon's
``/metrics`` endpoint, a one-shot run's ``--metrics-out`` dump, and the
nested ``/stats`` JSON all derive from the same registry, so a series
can never drift between surfaces.  Collectors are get-or-create: asking
for an existing name returns the existing collector (type mismatch is
an error), which lets independently-constructed components (scheduler,
writer, claims, admission, store) share series without coordination.

Three types, Prometheus semantics:

  * :class:`Counter`   — monotone ``inc``; rendered ``# TYPE ... counter``
  * :class:`Gauge`     — ``set``/``inc``/``dec``, or a callback sampled at
    render/snapshot time (for "current depth" readings like writer queue
    depth that live in another object); rendered ``gauge``
  * :class:`Histogram` — ``observe`` into cumulative buckets with
    ``_bucket``/``_sum``/``_count`` series; rendered ``histogram``

Gauges support a small label set (``labels(client="a")``) for the
per-client admission series; unlabeled use stays a plain method call.

Everything is thread-safe (one registry-wide lock for structure, one
lock per collector for values) and zero-dependency.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                   2.5, 5.0, 10.0, 30.0, 60.0, float("inf"))


def _fmt(v: float) -> str:
    """Prometheus-style number: integers render bare, floats as repr."""
    if v == float("inf"):
        return "+Inf"
    if float(v).is_integer():
        return str(int(v))
    return repr(float(v))


def _label_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class _Collector:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()

    def render(self, prefix: str) -> List[str]:  # pragma: no cover
        raise NotImplementedError

    def value_dict(self) -> Any:  # pragma: no cover
        raise NotImplementedError


class Counter(_Collector):
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._v = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name}: negative inc")
        with self._lock:
            self._v += amount

    def get(self) -> float:
        with self._lock:
            return self._v

    def value_dict(self) -> Any:
        v = self.get()
        return int(v) if v.is_integer() else v

    def render(self, prefix: str) -> List[str]:
        full = prefix + self.name
        return [f"# TYPE {full} counter", f"{full} {_fmt(self.get())}"]


class Gauge(_Collector):
    kind = "gauge"

    def __init__(self, name: str, help: str = "",
                 fn: Optional[Callable[[], float]] = None):
        super().__init__(name, help)
        self._v = 0.0
        self._fn = fn
        self._labeled: Dict[Tuple[Tuple[str, str], ...], float] = {}

    def set(self, value: float) -> None:
        with self._lock:
            self._v = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._v += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def set_function(self, fn: Callable[[], float]) -> None:
        with self._lock:
            self._fn = fn

    def set_labeled(self, value: float, **labels: str) -> None:
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            self._labeled[key] = float(value)

    def clear_labeled(self) -> None:
        with self._lock:
            self._labeled = {}

    def get(self) -> float:
        fn = self._fn
        if fn is not None:
            try:
                return float(fn())
            except Exception:
                return 0.0
        with self._lock:
            return self._v

    def value_dict(self) -> Any:
        with self._lock:
            labeled = dict(self._labeled)
        v = self.get()
        base = int(v) if float(v).is_integer() else v
        if not labeled:
            return base
        return {"value": base,
                "labeled": {_label_str(dict(k)): lv
                            for k, lv in labeled.items()}}

    def render(self, prefix: str) -> List[str]:
        full = prefix + self.name
        out = [f"# TYPE {full} gauge", f"{full} {_fmt(self.get())}"]
        with self._lock:
            labeled = dict(self._labeled)
        for key, v in sorted(labeled.items()):
            out.append(f"{full}{_label_str(dict(key))} {_fmt(v)}")
        return out


class Histogram(_Collector):
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help)
        bs = sorted(set(float(b) for b in buckets))
        if not bs or bs[-1] != float("inf"):
            bs.append(float("inf"))
        self.buckets = tuple(bs)
        self._counts = [0] * len(self.buckets)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._sum += v
            self._count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1

    def value_dict(self) -> Any:
        with self._lock:
            return {"count": self._count, "sum": self._sum,
                    "buckets": {_fmt(b): c for b, c in
                                zip(self.buckets, self._counts)}}

    def render(self, prefix: str) -> List[str]:
        full = prefix + self.name
        out = [f"# TYPE {full} histogram"]
        with self._lock:
            counts, s, n = list(self._counts), self._sum, self._count
        for b, c in zip(self.buckets, counts):
            out.append(f'{full}_bucket{{le="{_fmt(b)}"}} {c}')
        out.append(f"{full}_sum {_fmt(s)}")
        out.append(f"{full}_count {n}")
        return out


class Registry:
    """A namespaced set of collectors with one render/snapshot path.

    ``namespace`` is prepended (with ``_``) to every series at render
    time — the serve tier keeps its pinned ``repro_serve_*`` names by
    constructing ``Registry(namespace="repro_serve")`` while collector
    code refers to the short name (``cells_computed``).
    """

    def __init__(self, namespace: str = "repro"):
        self.namespace = namespace
        self._lock = threading.Lock()
        self._collectors: Dict[str, _Collector] = {}

    @property
    def _prefix(self) -> str:
        return self.namespace + "_" if self.namespace else ""

    def _get_or_create(self, cls, name: str, help: str,
                       **kwargs: Any) -> _Collector:
        with self._lock:
            c = self._collectors.get(name)
            if c is not None:
                if not isinstance(c, cls):
                    raise TypeError(
                        f"metric {name!r} already registered as "
                        f"{c.kind}, requested {cls.kind}")
                return c
            c = cls(name, help, **kwargs)
            self._collectors[name] = c
            return c

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "",
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        g = self._get_or_create(Gauge, name, help)
        if fn is not None:
            g.set_function(fn)
        return g

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._get_or_create(Histogram, name, help,
                                   buckets=buckets)

    def get(self, name: str) -> Optional[_Collector]:
        with self._lock:
            return self._collectors.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._collectors)

    # ------------------------------------------------------------ output
    def snapshot(self) -> Dict[str, Any]:
        """JSON-able {name: value} snapshot — counters/gauges as
        numbers, histograms as {count,sum,buckets}, labeled gauges as
        {value, labeled}.  The one-shot ``--metrics-out`` dump and the
        tests' registry-vs-Prometheus parity check both read this."""
        with self._lock:
            items = sorted(self._collectors.items())
        return {name: c.value_dict() for name, c in items}

    def render_prometheus(self) -> str:
        """Prometheus text exposition of every collector, namespaced."""
        lines: List[str] = []
        with self._lock:
            items = sorted(self._collectors.items())
        for _, c in items:
            lines.extend(c.render(self._prefix))
        return "\n".join(lines) + "\n"

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"namespace": self.namespace,
                       "metrics": self.snapshot()}, f, indent=2,
                      sort_keys=True)
            f.write("\n")


_PROCESS = Registry(namespace="repro")


def process_registry() -> Registry:
    """The process's own registry (namespace ``repro``): series that
    library code sets where no caller hands it a registry, such as the
    worker-sharded engine's build-time gauges."""
    return _PROCESS
