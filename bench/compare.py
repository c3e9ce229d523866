"""The comparisons that decide ``correct``: gaps between the system and
its plain reference, each a relative number that a limit is held against.
"""

from __future__ import annotations

import numpy as np


def series_gap(prog, ref) -> float:
    """max_t |prog - ref| / max_t |ref| of one per-round series (0 when
    both are all zero)."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if p.shape != r.shape:
        raise ValueError(f"series of shape {p.shape} against {r.shape}")
    if not (np.all(np.isfinite(p)) and np.all(np.isfinite(r))):
        return float("inf") if not np.array_equal(p, r,
                                                  equal_nan=True) else 0.0
    err = float(np.max(np.abs(p - r), initial=0.0))
    if err == 0.0:
        return 0.0
    return err / max(float(np.max(np.abs(r))), 1e-30)


def leaf_gap(prog: dict, ref: dict) -> float:
    """Worst leaf of ||prog - ref|| / ||ref|| over paired parameter leaves."""
    worst = 0.0
    for name, r in ref.items():
        p = np.asarray(prog[name], np.float64)
        r = np.asarray(r, np.float64)
        if not np.all(np.isfinite(p)):
            return float("inf")
        diff = float(np.linalg.norm(p - r))
        if diff:
            worst = max(worst, diff / max(float(np.linalg.norm(r)), 1e-30))
    return worst


def worst(values) -> float:
    values = list(values)
    return max(values) if values else 0.0


def history_gaps(prog: dict, ref: dict, series, early_rounds: int,
                 early_skip=("accuracy",)):
    """(early, whole) gaps of one cell's per-round history.

    ``early``: the worst series over the first ``early_rounds`` rounds,
    before rounding differences have been amplified by the training
    trajectory; a count of correct test samples (``accuracy``) moves in
    steps of one sample and is left out of it.  ``whole``: the worst
    series over every round.
    """
    early = worst(series_gap(np.asarray(prog[k])[:early_rounds],
                             np.asarray(ref[k])[:early_rounds])
                  for k in series if k not in early_skip)
    whole = worst(series_gap(prog[k], ref[k]) for k in series)
    return early, whole


def summarize(gaps: dict, limits: dict) -> dict:
    """The numbers compared: for each limit named ``<gap>_<stat>.<group>``
    (``early_max.random``, ``params_median.inflota``), the worst (``max``)
    or the median (``median``) of the cells' ``(gap, group)`` readings,
    beside its limit.  Only groups the run produced appear."""
    out = {}
    for name, limit in sorted(limits.items()):
        head, group = name.split(".", 1)
        gap, stat = head.rsplit("_", 1)
        values = gaps.get((gap, group))
        if not values:
            continue
        v = np.asarray(values, np.float64)
        out[name] = {"value": float(v.max() if stat == "max"
                                    else np.median(v)),
                     "limit": limit}
    return out
