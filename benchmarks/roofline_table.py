"""Roofline table from the dry-run artifacts (results/*.jsonl).

Prints, per (arch × shape × mesh): the three per-device roofline terms in
seconds, the dominant bottleneck, and MODEL_FLOPS / HLO_FLOPs (useful
fraction — catches remat/redundancy waste).
"""

from __future__ import annotations

import glob
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def load(paths=None):
    paths = paths or sorted(glob.glob(os.path.join(RESULTS, "*.jsonl")))
    rows, seen = [], set()
    for p in paths:
        with open(p) as f:
            for line in f:
                m = json.loads(line)
                key = (m["arch"], m["shape"], m["mesh"],
                       m.get("variant", ""))
                if key in seen:
                    continue
                seen.add(key)
                rows.append(m)
    return rows


def run(paths=None):
    rows = []
    for m in load(paths):
        rf = m["roofline"]
        n_chips = 1
        for d in m["mesh"].split("x"):
            n_chips *= int(d)
        useful = (m.get("model_flops", 0.0) / n_chips / rf["flops"]
                  if rf["flops"] else 0.0)
        tag = f"{m['arch']}:{m['shape']}:{m['mesh']}"
        if m.get("variant"):
            tag += f":{m['variant']}"
        rows += [
            {"name": tag, "metric": "compute_s",
             "value": f"{rf['compute_s']:.4g}"},
            {"name": tag, "metric": "memory_s",
             "value": f"{rf['memory_s']:.4g}"},
            {"name": tag, "metric": "collective_s",
             "value": f"{rf['collective_s']:.4g}"},
            {"name": tag, "metric": "bottleneck", "value": rf["bottleneck"]},
            {"name": tag, "metric": "useful_flops_frac",
             "value": f"{useful:.3f}"},
        ]
    if not rows:
        rows.append({"name": "roofline", "metric": "status",
                     "value": "no dry-run artifacts under results/ "
                              "(run python -m repro.launch.dryrun --all)"})
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
