"""Roofline terms from a compiled dry-run artifact.

Hardware model: the ``PEAKS`` table, keyed by ``device_kind`` as JAX
reports it.  TPU v5e ("TPU v5 lite"; Google Cloud documentation, "TPU
v5e") —
  peak_flops   197e12 FLOP/s (bf16)
  hbm_bw       819e9  B/s
  ici_bw       50e9   B/s per link (per-device collective payload charged
               against one link; the conservative single-link convention)
A device that is not in the table is an error, never a default.

``compiled.cost_analysis()`` counts every while-loop (scan) body ONCE, so
for the scanned layer stacks it understates per-step work by ~n_layers.
We therefore do our own trip-weighted walk of the optimized HLO:

  * build the computation call graph (while body/condition, fusion calls,
    reduce to_apply, conditional branches),
  * propagate execution weights from ENTRY, multiplying by the while ops'
    ``known_trip_count`` backend_config,
  * FLOPs: 2·M·N·K for every ``dot`` in any computation × its weight
    (dots dominate every model in the zoo; elementwise flops are ignored,
    matching the usual MFU convention),
  * bytes: operand + result bytes of every *traffic-level* op (ENTRY,
    while bodies/conds, conditional branches — i.e. buffers that live in
    HBM) × weight; ops inside fusions stay in registers/VMEM and are
    skipped, so this approximates post-fusion HBM traffic,
  * collectives: ring-cost payloads × weight —
      all-reduce        2·size·(n-1)/n
      all-gather        size·(n-1)/n        (size = result bytes)
      reduce-scatter    size·(n-1)          (size = result bytes)
      all-to-all        size·(n-1)/n
      collective-permute size
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Set, Tuple


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float     # FLOP/s, bf16
    hbm_bw: float    # B/s
    ici_bw: float    # B/s per link


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for unknown kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# ops that never touch HBM by themselves
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id",
             "opt-barrier"}


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    elems_total, bytes_total = 0, 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems_total += n
        bytes_total += n * _DTYPE_BYTES[dt]
    return elems_total, bytes_total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class _Op:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    line: str


_OP_RE = re.compile(
    r"(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"          # result name
    r"((?:\([^()]*\)|[a-z]\d*[a-z0-9]*\[[\d,]*\](?:\{[^}]*\})?))\s+"
    r"([\w\-]+)\(")                              # opcode


def _parse_ops(body_lines: List[str]) -> List[_Op]:
    ops = []
    for line in body_lines:
        s = line.strip()
        m = _OP_RE.match(s)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        # operand segment: first (...) after the opcode
        start = s.find(opcode + "(") + len(opcode) + 1
        depth, end = 1, start
        while end < len(s) and depth:
            if s[end] == "(":
                depth += 1
            elif s[end] == ")":
                depth -= 1
            end += 1
        seg = s[start:end - 1]
        operands = re.findall(r"%([\w.\-]+)", seg)
        ops.append(_Op(name, type_str, opcode, operands, s))
    return ops


_HDR_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")


def _split(hlo_text: str):
    """-> (comps: name -> [op lines], entry: str)."""
    comps: Dict[str, List[str]] = {}
    entry, name = None, None
    for line in hlo_text.splitlines():
        m = _HDR_RE.match(line)
        if m:
            name = m.group(2)
            comps[name] = []
            if m.group(1):
                entry = name
        elif name is not None and line.strip() == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _trip_count(line: str) -> float:
    m = re.search(r'known_trip_count[^0-9]*(\d+)', line)
    return float(m.group(1)) if m else 1.0


_EDGE_RES = [
    ("body", re.compile(r"body=%?([\w.\-]+)")),
    ("cond", re.compile(r"condition=%?([\w.\-]+)")),
    ("call", re.compile(r"calls=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")),
    ("apply", re.compile(r"to_apply=%?([\w.\-]+)")),
    ("branch", re.compile(r"branch_computations=\{([^}]*)\}")),
]


@dataclasses.dataclass
class HloAnalysis:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collectives: Dict[str, float]
    collective_counts: Dict[str, int]


def analyze_hlo(hlo_text: str) -> HloAnalysis:
    comps, entry = _split(hlo_text)
    parsed = {name: _parse_ops(lines) for name, lines in comps.items()}

    # ---- propagate execution weights through the call graph -------------
    weights: Dict[str, float] = {name: 0.0 for name in comps}
    traffic: Set[str] = set()
    if entry is None:
        entry = next(iter(comps), None)
    if entry is None:
        return HloAnalysis(0, 0, 0, {}, {})
    weights[entry] = 1.0
    traffic.add(entry)
    # iterate to fixed point (call graphs are DAGs; a few passes suffice)
    for _ in range(12):
        changed = False
        for name, ops in parsed.items():
            w = weights.get(name, 0.0)
            if w == 0.0:
                continue
            for op in ops:
                for kind, rx in _EDGE_RES:
                    for m in rx.finditer(op.line):
                        targets = re.findall(r"[\w.\-]+", m.group(1))
                        for tgt in targets:
                            tgt = tgt.lstrip("%")
                            if tgt not in weights:
                                continue
                            mult = _trip_count(op.line) if kind in (
                                "body", "cond") else 1.0
                            nw = w * mult
                            if nw > weights[tgt]:
                                weights[tgt] = nw
                                changed = True
                            if kind in ("body", "cond", "branch"):
                                if tgt not in traffic and name in traffic:
                                    traffic.add(tgt)
                                    changed = True
        if not changed:
            break

    flops = 0.0
    hbm = 0.0
    coll_b: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    coll_c: Dict[str, int] = {k: 0 for k in _COLLECTIVES}

    for name, ops in parsed.items():
        w = weights.get(name, 0.0)
        if w == 0.0:
            continue
        shapes = {op.name: op.type_str for op in ops}
        for op in ops:
            # ------------------------------------------------ FLOPs (dots)
            if op.opcode == "dot" and op.operands:
                out_elems, _ = _shape_elems_bytes(op.type_str)
                lhs_type = shapes.get(op.operands[0], "")
                lhs_dims = _shape_dims(lhs_type)
                mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
                k = 1
                if mc and lhs_dims:
                    for idx in mc.group(1).split(","):
                        if idx and int(idx) < len(lhs_dims):
                            k *= lhs_dims[int(idx)]
                flops += w * 2.0 * out_elems * k
            # --------------------------------------------------- traffic
            if name in traffic and op.opcode not in _FREE_OPS:
                _, out_b = _shape_elems_bytes(op.type_str)
                if op.opcode in ("dynamic-slice", "slice", "gather"):
                    # reads only the sliced/gathered elements, not the
                    # whole operand (KV caches, stacked scan params)
                    hbm += w * 2.0 * out_b
                elif op.opcode in ("dynamic-update-slice", "scatter"):
                    upd = (_shape_elems_bytes(
                        shapes.get(op.operands[1], ""))[1]
                        if len(op.operands) > 1 else out_b)
                    hbm += w * 2.0 * upd
                elif op.opcode == "while":
                    # loop carries live in place; charge one read + write
                    in_b = sum(_shape_elems_bytes(shapes.get(o, ""))[1]
                               for o in op.operands)
                    hbm += out_b + in_b
                else:
                    in_b = sum(_shape_elems_bytes(shapes.get(o, ""))[1]
                               for o in op.operands)
                    hbm += w * (out_b + in_b)
            # ----------------------------------------------- collectives
            kind = next((c for c in _COLLECTIVES
                         if op.opcode.startswith(c)), None)
            if kind and not op.opcode.endswith("-done"):
                _, size = _shape_elems_bytes(op.type_str)
                n = _group_size(op.line)
                if kind == "all-reduce":
                    payload = 2.0 * size * (n - 1) / max(n, 1)
                elif kind == "all-gather":
                    payload = size * (n - 1) / max(n, 1)
                elif kind == "reduce-scatter":
                    payload = float(size) * (n - 1)
                elif kind == "all-to-all":
                    payload = size * (n - 1) / max(n, 1)
                else:
                    payload = float(size)
                coll_b[kind] += w * payload
                coll_c[kind] += 1
    return HloAnalysis(flops, hbm, sum(coll_b.values()), coll_b, coll_c)


def _group_size(line: str) -> int:
    """Participant count per replica group of a collective op line."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:  # iota form [G,S]<=[...]
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2


# --------------------------------------------------------------- interface

@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    collectives: Dict[str, float]
    collective_counts: Dict[str, int]
    xla_flops_once: float          # cost_analysis (bodies counted once)
    xla_bytes_once: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def analyze(compiled, device_kind: str) -> Roofline:
    """Derive the three per-device roofline terms of an executable on
    ``device_kind`` (a key of ``PEAKS``)."""
    pk = peaks(device_kind)
    cost = compiled.cost_analysis()
    an = analyze_hlo(compiled.as_text())
    compute_s = an.flops / pk.flops
    memory_s = an.hbm_bytes / pk.hbm_bw
    collective_s = an.collective_bytes / pk.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    return Roofline(
        flops=an.flops, bytes_accessed=an.hbm_bytes,
        collective_bytes=an.collective_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=max(terms, key=terms.get),
        collectives=an.collectives, collective_counts=an.collective_counts,
        xla_flops_once=float(cost.get("flops", 0.0)),
        xla_bytes_once=float(cost.get("bytes accessed", 0.0)))


def model_flops(cfg, n_tokens: int) -> float:
    """6·N_active·D — the 'useful' training FLOPs convention."""
    return 6.0 * cfg.active_param_count() * n_tokens
