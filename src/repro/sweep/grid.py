"""Declarative experiment grids -> vmappable cohorts -> one computation.

A ``SweepSpec`` names a grid: ``axes`` (axis name -> values, crossed) over
a ``base`` of fixed fields.  Cells split into *cohorts* by their static
fields — everything that changes compiled structure (policy / channel
model, task, rounds, case, k_b, backend).  Everything else becomes a
traced per-experiment operand, so a whole cohort is ONE computation:
``fl.trainer.scan_experiment`` lifted over a leading experiment axis with
``jax.vmap``, jitted once, and sharded over the device mesh by
``repro.sweep.shard``.  The jitted program is kept per trace key
(``prepare_cohort``), so a later cohort that differs only in its seeds
or varying scalar values skips trace, lowering and the data build.

Two families of axes vectorize inside a cohort:

  * VECTOR_AXES — scalars (``seed``, ``lr``, ``sigma2``, ``p_max``,
    ``eps``, ``rho``, ``L``).  ``eps`` / ``rho`` re-parameterize the
    channel factory per experiment (``ImperfectCSI.eps`` /
    ``GaussMarkovFading.rho`` accept traced scalars); ``sigma2`` / ``L``
    reach the Pallas kernels as traced operands in a VMEM row, so even
    ``backend="pallas"`` cohorts sweep them without recompiling.
  * DATA_AXES — ``U``, ``k_bar``, ``data_seed``.  Cells whose worker
    fleets differ merge into a RAGGED cohort: every cell's worker data is
    padded to the cohort-wide (U_max, K_max) with per-experiment worker
    masks (``wmask``), and the engine silences padded workers end to end
    (zero k_i / p_max, masked selection).  All worker-axis randomness is
    restriction-stable (``repro.core.channel.worker_keys``), so a padded
    cell is BIT-EXACT against its standalone ``FLTrainer`` run.

Cells that can't be ragged-merged stay shape-exact: only channels whose
model reports ``ragged_exact = False`` (e.g. pathloss — ensemble-
normalized) remain excluded.  Minibatch (``k_b``) and SGD cells merge
too: sample draws are restriction-stable per-sample ``fold_in``
(``fl.client.minibatch_indices``) and the SGD numerator counts real
workers, not the padded array extent.

Compared to the old benchmark drivers (one ``FLTrainer`` per cell: a
fresh trace + compile + U-round dispatch chain each), a cohort of E
experiments compiles once and runs device-resident end to end — and a
full U x eps x sigma2 grid is ONE compile per backend instead of one per
(U, eps) combination.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import shutil
import sys
import threading
import time
from collections import OrderedDict
from typing import (Any, Dict, List, Mapping, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as chan_lib
from repro.core import selection as sel_lib
from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data, dim_hint
from repro.fl.trainer import (FLConfig, pad_workers, scan_experiment,
                              scan_experiment_block, scan_experiment_init)
from repro.obs import trace as obs_trace
from repro.sweep import shard as shard_lib
from repro.sweep import store as store_lib

# Cell fields that may vary WITHIN a cohort as traced scalar operands.
VECTOR_AXES = ("seed", "lr", "sigma2", "p_max", "eps", "rho", "L")

# The pre-ragged (PR 3) vector set: eps / rho / L were static model
# fields and every distinct value compiled its own cohort.  Kept for the
# before/after cohort-count benchmark (``cohorts(..., legacy=True)``).
LEGACY_VECTOR_AXES = ("seed", "lr", "sigma2", "p_max")

# Cell fields that reshape the worker fleet: they merge into a ragged
# cohort (padded worker axis + per-experiment masks) when the cell is
# ragged-mergeable (see ``ragged_mergeable``).
DATA_AXES = ("U", "k_bar", "data_seed")

# Scalar fields handled by the uniform/varying split in ``run_cohort``.
# The trailing three may be None (= "not set"): None never vectorizes.
_SCALARS = ("lr", "sigma2", "p_max", "eps", "rho", "L")

DEFAULTS: Dict[str, Any] = {
    "task": "linreg",        # repro.data.tasks registry name
    "U": 20,
    "k_bar": 30,
    "data_seed": 0,
    "rounds": 100,
    "eval_every": 1,
    "policy": "inflota",     # registry name | RoundPolicy instance
    "channel": None,         # None | registry name | ChannelModel instance
    "case": Case.GD_CONVEX,  # Case | its string value
    "k_b": None,
    "backend": "auto",
    "select_prob": 0.5,
    "constants": None,       # None -> LearningConstants(sigma2=sigma2[, L])
    "amplitude": False,
    "h_floor": 1e-3,
    "seed": 0,
    "lr": 0.1,
    "sigma2": 1e-4,
    "p_max": 10.0,
    "eps": None,             # CSI error: channel factory kwarg (traced)
    "rho": None,             # fading correlation: factory kwarg (traced)
    "L": None,               # smoothness constant: None = constants default
    "U_shards": None,        # worker-sharded engine: S shard blocks over
                             # the worker axis; None = dense (U, D) engine
}


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A declarative experiment grid.

    axes:  axis name -> tuple of values; the grid is their cross product.
           Axis names must be cell fields (see DEFAULTS).
    base:  fixed cell fields overriding DEFAULTS for every cell.
    eval:  collect per-round task metrics against the task's test split.
    tail:  window (in eval points) for the ``<metric>_tail`` summary.
    """

    axes: Mapping[str, Sequence[Any]]
    base: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    eval: bool = True
    tail: int = 10

    def __post_init__(self):
        known = set(DEFAULTS)
        bad = [k for k in (*self.axes, *self.base) if k not in known]
        if bad:
            raise ValueError(
                f"unknown cell field(s) {bad}; known: {sorted(known)}")
        empty = [k for k, v in self.axes.items() if len(tuple(v)) == 0]
        if empty:
            raise ValueError(f"empty axis value list for {empty}")


@dataclasses.dataclass
class Cohort:
    """Cells that share every static field -> one vmapped computation."""

    static: Dict[str, Any]
    cells: List[Dict[str, Any]]     # grid order preserved
    indices: List[int]              # positions in the full cell list

    def __len__(self) -> int:
        return len(self.cells)

    def data_keys(self) -> List[Tuple]:
        """Unique (task, U, k_bar, data_seed) configs, cohort order."""
        seen: Dict[Tuple, None] = {}
        for c in self.cells:
            seen.setdefault(_data_key(c))
        return list(seen)

    @property
    def ragged(self) -> bool:
        """True when the cohort spans more than one worker-fleet shape."""
        return len(self.data_keys()) > 1


def cohort_cost(cohort: Cohort) -> int:
    """Scheduler cost estimate: cells x rounds x U_max x D.

    Deliberately cheap — no task data is built; D comes from
    ``repro.data.tasks.dim_hint``.  The async runtime uses this only to
    ORDER dispatch (costliest cohorts first, so the expensive compiles
    start while cheaper cohorts fill the remaining slots); a bad estimate
    costs wall clock, never correctness.
    """
    u_max = max(int(c["U"]) for c in cohort.cells)
    return (len(cohort.cells) * int(cohort.static["rounds"]) * u_max
            * dim_hint(cohort.static.get("task")))


def cells(spec: SweepSpec) -> List[Dict[str, Any]]:
    """The full grid, one dict per cell, axes crossed in insertion order."""
    names = list(spec.axes)
    out: List[Dict[str, Any]] = []

    def rec(i: int, acc: Dict[str, Any]):
        if i == len(names):
            out.append({**DEFAULTS, **dict(spec.base), **acc})
            return
        for v in spec.axes[names[i]]:
            rec(i + 1, {**acc, names[i]: v})

    rec(0, {})
    return out


def _data_key(cell: Dict[str, Any]) -> Tuple:
    return (cell["task"], cell["U"], cell["k_bar"], cell["data_seed"])


def ragged_mergeable(cell: Dict[str, Any]) -> bool:
    """Whether this cell may join a ragged (padded-worker-axis) cohort.

    One exclusion remains: channel models that report
    ``ragged_exact = False`` (cross-worker coupling, e.g. pathloss
    ensemble normalization), where padding would not be bit-exact against
    the cell's standalone run.

    The historical ``k_b`` / SGD exclusions are LIFTED: minibatch draws
    are restriction-stable (``fl.client.minibatch_indices`` derives each
    sample's priority from ``fold_in(key, sample_index)``, so K_max
    padding never shifts a draw) and eq. 37's leading U counts real
    workers (``k_i > 0``) rather than the padded array extent.

    Worker-sharded cells (``U_shards`` set) stay shape-exact: padding
    the worker axis to a cohort U_max would change the shard blocking
    (U_max / S workers per block instead of U / S), shifting the f32
    reassociation of the per-shard superposition partials — the cohort
    would no longer be bit-identical to the cells' standalone runs.
    """
    return (chan_lib.ragged_exact(cell["channel"])
            and cell.get("U_shards") is None)


def _static_key(cell: Dict[str, Any], legacy: bool = False) -> Tuple:
    drop = set(LEGACY_VECTOR_AXES if legacy else VECTOR_AXES)
    if not legacy and ragged_mergeable(cell):
        drop |= set(DATA_AXES)
    return tuple((k, cell[k]) for k in sorted(cell) if k not in drop)


def cohorts(cell_list: List[Dict[str, Any]],
            indices: Optional[List[int]] = None, *,
            legacy: bool = False) -> List[Cohort]:
    """Group cells by static key, preserving grid order within a cohort.

    ``legacy=True`` reproduces the pre-ragged (PR 3) partitioning —
    U / k_bar / data_seed / eps / rho / L as static fields — kept for the
    cohort-count before/after benchmark and for debugging shape-exact
    plans.
    """
    indices = list(range(len(cell_list))) if indices is None else indices
    groups: Dict[Tuple, Cohort] = {}
    for idx, cell in zip(indices, cell_list):
        key = _static_key(cell, legacy)
        if key not in groups:
            groups[key] = Cohort(
                static={k: v for k, v in key}, cells=[], indices=[])
        groups[key].cells.append(cell)
        groups[key].indices.append(idx)
    return list(groups.values())


def _resolved_case(case) -> Case:
    return case if isinstance(case, Case) else Case(case)


def _split_scalars(cohort_cells: List[Dict[str, Any]]
                   ) -> Tuple[Dict[str, Any], Dict[str, jnp.ndarray]]:
    """Partition the scalar cell fields into uniform values and traced
    per-experiment operand arrays (only fields that actually vary trace —
    uniform scalars stay Python floats so the per-run graph matches
    FLTrainer's exactly)."""
    uniform: Dict[str, Any] = {}
    varying: Dict[str, jnp.ndarray] = {}
    for name in _SCALARS:
        vals = [c[name] for c in cohort_cells]
        if any(v is None for v in vals):
            if not all(v is None for v in vals):
                raise ValueError(
                    f"cell field {name!r} mixes None with numbers inside "
                    f"one cohort; use an explicit number (e.g. 0.0) for "
                    f"every cell")
            uniform[name] = None
        elif len({float(v) for v in vals}) == 1:
            uniform[name] = float(vals[0])
        else:
            varying[name] = jnp.asarray([float(v) for v in vals],
                                        jnp.float32)
    return uniform, varying


def _cohort_cfg(static: Dict[str, Any], s: Dict[str, Any],
                u: int) -> FLConfig:
    """FLConfig for one experiment of a cohort.

    ``s`` maps scalar field -> value (Python float, None, or a traced
    per-experiment scalar); ``u`` is the worker count the channel model
    is sized for (the cohort's U_max when ragged).
    """
    chanc = ChannelConfig(sigma2=s["sigma2"], p_max=s["p_max"],
                          amplitude=static["amplitude"],
                          h_floor=static["h_floor"])
    model = static["channel"]
    factory_kw = {k: s[k] for k in ("eps", "rho") if s[k] is not None}
    if factory_kw:
        # eps / rho re-parameterize the channel per experiment; resolve
        # here (build_engine would resolve without the kwargs)
        model = chan_lib.resolve_model(model, u, chanc, **factory_kw)
    constants = static["constants"]
    if constants is None:
        ckw: Dict[str, Any] = {"sigma2": s["sigma2"]}
        if s["L"] is not None:
            ckw["L"] = s["L"]
        constants = LearningConstants(**ckw)
    elif s["L"] is not None:
        raise ValueError(
            "cell field 'L' conflicts with explicitly provided constants; "
            "set L through LearningConstants OR the cell field, not both")
    return FLConfig(rounds=static["rounds"], lr=s["lr"],
                    policy=static["policy"],
                    case=_resolved_case(static["case"]),
                    k_b=static["k_b"], channel=chanc,
                    channel_model=model, constants=constants,
                    select_prob=static["select_prob"],
                    backend=static["backend"], scan=True,
                    eval_every=static["eval_every"],
                    worker_sharding=static["U_shards"])


def _pad_worker_axis(a: jnp.ndarray, u_max: int) -> jnp.ndarray:
    pad = [(0, u_max - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def _ragged_uniques(cohort: Cohort, built: Dict[Tuple, Any], do_eval: bool,
                    eval_override
                    ) -> Tuple[Dict[str, jnp.ndarray], bool]:
    """Deduplicated worker data for a ragged cohort.

    Every UNIQUE dataset (``data_keys``: task x U x k_bar x data_seed) is
    padded to the cohort-wide (U_max, K_max) exactly once and stacked
    into ``uniques`` (leading axis = unique dataset, NOT experiment);
    each experiment carries only an i32 index ``didx`` into that stack
    (:func:`_cohort_batch`).  ``run_one`` gathers its cell's block by
    index, so an 8-seed x 3-U cohort holds 3 padded copies of the worker
    data instead of 24 — the gather returns the identical padded arrays,
    so results are unchanged bit-for-bit.

    Returns (uniques, batch_eval): ``uniques`` are closed over by
    ``run_one`` (replicated).  Per-key test splits dedup the same way
    unless an ``eval_override`` supplies one shared set.
    """
    if any(not isinstance(c["channel"], (str, type(None)))
           for c in cohort.cells):
        raise ValueError(
            "ragged cohorts need a registry channel name or None: an "
            "instance is sized for one worker count and cannot span "
            "cells with different U")
    keys = cohort.data_keys()
    u_max = max(len(built[k][1]) for k in keys)
    k_max = max(int(np.asarray(x).shape[0])
                for key in keys
                for x, _ in built[key][1])
    per_key: Dict[Tuple, Tuple] = {}
    for key in keys:
        _, workers, test = built[key]
        X, Y, mask, k_i = pad_workers(workers, k_max=k_max)
        u = len(workers)
        wmask = jnp.asarray(
            np.arange(u_max) < u, jnp.float32)
        per_key[key] = (
            _pad_worker_axis(X, u_max), _pad_worker_axis(Y, u_max),
            _pad_worker_axis(mask, u_max), _pad_worker_axis(k_i, u_max),
            wmask, test)

    def stack(i):
        return jnp.stack([per_key[k][i] for k in keys])

    uniques = {"X": stack(0), "Y": stack(1), "mask": stack(2),
               "k_i": stack(3), "wmask": stack(4)}
    batch_eval = do_eval and eval_override is None
    if batch_eval:
        uniques["ex"] = jnp.stack(
            [jnp.asarray(per_key[k][5][0]) for k in keys])
        uniques["ey"] = jnp.stack(
            [jnp.asarray(per_key[k][5][1]) for k in keys])
    return uniques, batch_eval


def _cohort_batch(cohort: Cohort, varying: Dict[str, jnp.ndarray]
                  ) -> Dict[str, jnp.ndarray]:
    """The per-experiment operands of one cohort: its cells' PRNG keys,
    the scalars that vary, and (ragged) each cell's index into the
    unique datasets.  Leaves lead with the experiment axis."""
    batch = {"key": jnp.stack([jax.random.PRNGKey(int(c["seed"]))
                               for c in cohort.cells]), **varying}
    if cohort.ragged:
        pos = {k: i for i, k in enumerate(cohort.data_keys())}
        batch["didx"] = jnp.asarray(
            [pos[_data_key(c)] for c in cohort.cells], jnp.int32)
    return batch


@dataclasses.dataclass
class _CohortContext:
    """The shared host-side preparation behind both execution styles.

    ``data_of(batch_slice)`` -> (X, Y, mask, k_i, wmask, eval_xy) and
    ``cfg_of(batch_slice)`` -> FLConfig are the two closures every
    per-experiment function composes; factoring them out guarantees the
    whole-scan path (:func:`prepare_cohort`) and the checkpointed block
    path (:func:`prepare_cohort_phases`) feed ``scan_experiment*`` the
    exact same operands — the root of the blocked-run bit-identity
    guarantee.
    """

    task: Any
    data_of: Any
    cfg_of: Any
    nbytes: int                     # of the data the closures hold


def _cohort_context(cohort: Cohort, uniform: Dict[str, Any],
                    varying: Sequence[str], *, do_eval: bool = True,
                    eval_data=None) -> _CohortContext:
    """Build the cohort's task data and close ``data_of`` / ``cfg_of``
    over it, the ``uniform`` scalar values and the names of the
    ``varying`` ones (read from the batch)."""
    st = cohort.static
    built = {key: build_task_data(key[0], U=key[1], k_bar=key[2],
                                  data_seed=key[3])
             for key in cohort.data_keys()}
    task = next(iter(built.values()))[0]
    ragged = cohort.ragged
    if st["k_b"] is not None:
        # the engine's own k_b guard is skipped under trace (ragged
        # cohorts pass traced masks), so validate against the concrete
        # fleets here — before any compile is paid
        min_k = min(int(np.asarray(x).shape[0])
                    for key in cohort.data_keys()
                    for x, _ in built[key][1])
        if int(st["k_b"]) > min_k:
            raise ValueError(
                f"k_b={st['k_b']} exceeds the smallest worker's sample "
                f"count ({min_k}) in this cohort")

    u_model = (max(len(built[k][1]) for k in cohort.data_keys()) if ragged
               else len(built[cohort.data_keys()[0]][1]))

    def cfg_of(batch):
        s = {**uniform, **{n: batch[n] for n in varying}}
        return _cohort_cfg(st, s, u_model)

    if ragged:
        uniq, batch_eval = _ragged_uniques(cohort, built, do_eval,
                                           eval_data)
        shared_eval = (jnp.asarray(eval_data[0]), jnp.asarray(eval_data[1])
                       ) if (do_eval and eval_data is not None) else None

        def data_of(batch):
            d = batch["didx"]
            eval_xy = ((uniq["ex"][d], uniq["ey"][d]) if batch_eval
                       else shared_eval)
            return (uniq["X"][d], uniq["Y"][d], uniq["mask"][d],
                    uniq["k_i"][d], uniq["wmask"][d], eval_xy)

        held = (uniq, shared_eval)
    else:
        # uniform-fleet cohorts keep the data in the closure (not
        # batched), so their per-run graph — and results — are identical
        # to the pre-ragged engine
        _, workers, test = built[cohort.data_keys()[0]]
        X, Y, mask, k_i = pad_workers(workers)
        if eval_data is not None:
            test = eval_data
        eval_xy = ((jnp.asarray(test[0]), jnp.asarray(test[1]))
                   if do_eval else None)

        def data_of(batch):
            return (X, Y, mask, k_i, None, eval_xy)

        held = (X, Y, mask, k_i, eval_xy)
    return _CohortContext(task=task, data_of=data_of, cfg_of=cfg_of,
                          nbytes=sum(a.nbytes for a in jax.tree.leaves(held)))


# How many prepared cohort programs a process keeps, and how many bytes
# of data they may hold; the least recently used is dropped first.  An
# entry holds its worker data on the device (about 10 MB for the paper's
# MLP) and one more copy inside each executable it compiled, which
# embeds the data as constants.  An entry larger than the byte bound is
# not kept: its cohort runs, and the next one like it builds anew.  A
# cohort that large spends more on its data and its run than on a trace.
COHORT_FN_CACHE_SIZE = 8
COHORT_FN_CACHE_BYTES = 256 << 20


class _CohortProgram:
    """One trace key's per-experiment function, the data it closes over,
    and its jitted batched callables, one per (mesh, donation)."""

    def __init__(self, ctx: _CohortContext, static: str):
        self.fns: Dict[Tuple, Any] = {}
        self.compiled: set = set()     # (mesh, donate, batch shapes) run
        self.data_bytes = ctx.nbytes

        def run_one(batch):
            # the body runs only while JAX traces it: one event per trace
            obs_trace.event("cohort.trace", cat="sweep", static=static)
            X, Y, mask, k_i, wmask, eval_xy = ctx.data_of(batch)
            return scan_experiment(ctx.task, X, Y, mask, k_i,
                                   ctx.cfg_of(batch), batch["key"],
                                   eval_xy=eval_xy, wmask=wmask)

        self.run_one = run_one

    @property
    def nbytes(self) -> int:
        """The data, and the copy each compiled executable embeds."""
        return self.data_bytes * (1 + len(self.compiled))


class _CohortFnCache:
    """Process-wide LRU of :class:`_CohortProgram` by trace key, bounded
    in entries and bytes; safe to use from several dispatch threads at
    once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: "OrderedDict[Tuple, _CohortProgram]" = OrderedDict()

    def _evict(self) -> None:
        # under the lock; a dropped program lives on in the cohorts that
        # hold it until they are done with it
        while self._programs and (
                len(self._programs) > COHORT_FN_CACHE_SIZE
                or sum(p.nbytes for p in self._programs.values())
                > COHORT_FN_CACHE_BYTES):
            self._programs.popitem(last=False)

    def program(self, key: Tuple, build) -> _CohortProgram:
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                return prog
        built = build()              # the data build, outside the lock
        with self._lock:
            # a thread that missed on the same key at once may have won
            prog = self._programs.setdefault(key, built)
            self._programs.move_to_end(key)
            self._evict()
        return prog

    def callable(self, prog: _CohortProgram, mesh, donate: bool,
                 shapes: Tuple) -> Tuple[Any, bool]:
        with self._lock:
            fn = prog.fns.get((mesh, donate))
            if fn is None:
                fn = prog.fns[(mesh, donate)] = shard_lib.batched_program(
                    prog.run_one, mesh, donate=donate)
            hit = (mesh, donate, shapes) in prog.compiled
            if not hit:
                prog.compiled.add((mesh, donate, shapes))
                self._evict()
        return fn, hit

    def info(self) -> Dict[str, int]:
        with self._lock:
            return {"maxsize": COHORT_FN_CACHE_SIZE,
                    "currsize": len(self._programs),
                    "maxbytes": COHORT_FN_CACHE_BYTES,
                    "nbytes": sum(p.nbytes for p in self._programs.values())}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


_COHORT_FNS = _CohortFnCache()


def cohort_fn_cache_info() -> Dict[str, int]:
    """The cache's bounds, the programs it holds and their bytes."""
    return _COHORT_FNS.info()


def cohort_fn_cache_clear() -> None:
    """Drop every cached cohort program."""
    _COHORT_FNS.clear()


def _static_trace_key(static: Dict[str, Any]) -> Tuple:
    """The static fields as the trace sees them.  Each value is held
    with its type (so ``16`` and ``16.0`` differ, and an object's id is
    never reused while the key lives; a dataclass compares equal only to
    one of its own class), and a policy or channel given by name with
    the factory registered under it now."""
    registries = {"policy": sel_lib._POLICY_REGISTRY,
                  "channel": chan_lib._CHANNEL_REGISTRY}
    return tuple(
        (k, type(v), v, registries[k].get(v)
         if k in registries and isinstance(v, str) else None)
        for k, v in static.items())


def _content_digest(arrays) -> Optional[str]:
    if arrays is None:
        return None
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class PreparedCohort:
    """A cohort's batch and the cached program it runs.

    ``jax.vmap(run_one)`` applied to ``batch`` IS the cohort's whole
    computation; :func:`dispatch_cohort` runs it through the program's
    jitted callable, kept per mesh and donation and shared by every
    cohort of the same trace key.  The split from :func:`run_cohort`
    exists so the async runtime (``repro.runtime``) can stage host-side
    preparation, device dispatch, and result finalization on different
    threads while the serial path composes the same three pieces in
    order — per-cell results are identical by construction.
    """

    cohort: Cohort
    program: _CohortProgram
    batch: Dict[str, jnp.ndarray]  # leaves lead with the experiment axis

    @property
    def run_one(self):
        """The per-experiment function of a batch slice."""
        return self.program.run_one


def prepare_cohort(cohort: Cohort, *, do_eval: bool = True,
                   eval_data=None) -> PreparedCohort:
    """Host-side phase: the cohort's batch, and its program from the
    process's cache, built on a miss (task data, padding, the
    per-experiment closure).  No device computation is dispatched.

    The cache key holds everything the traced program depends on apart
    from the batch shapes, which ``jax.jit`` keys itself: the static
    field values (:func:`_static_trace_key`), the uniform scalar values
    and the names of the varying ones, the datasets in order (and so
    whether the cohort is ragged), ``do_eval``, a digest of
    ``eval_data`` and the 64-bit mode the data were built in.  Cohorts
    that differ only in their seeds or in the values of varying scalars
    share one program.
    """
    uniform, varying = _split_scalars(cohort.cells)
    key = (_static_trace_key(cohort.static), tuple(uniform.items()),
           tuple(varying), tuple(cohort.data_keys()), bool(do_eval),
           _content_digest(eval_data), bool(jax.config.jax_enable_x64))

    def build() -> _CohortProgram:
        ctx = _cohort_context(cohort, uniform, tuple(varying),
                              do_eval=do_eval, eval_data=eval_data)
        return _CohortProgram(ctx, cohort_static_hash(cohort))

    return PreparedCohort(cohort=cohort,
                          program=_COHORT_FNS.program(key, build),
                          batch=_cohort_batch(cohort, varying))


def dispatch_cohort(prep: PreparedCohort, mesh=None, *,
                    donate: bool = False, registry=None
                    ) -> Tuple[Any, Optional[int], bool]:
    """Dispatch phase: run the cohort's cached jitted callable for
    ``mesh`` and ``donate`` over its batch, WITHOUT waiting for results
    (:func:`repro.sweep.shard.dispatch_sharded`).

    Returns ``(out, e, hit)``: ``hit`` says the program already ran on
    batches of these shapes for this mesh and donation, so JAX's trace
    cache serves it; on a miss it traces, lowers and compiles (or reads
    the compile cache).  ``registry`` counts ``cohort_fn_hits`` /
    ``cohort_fn_misses``.
    """
    shapes = tuple((k, v.shape, v.dtype)
                   for k, v in sorted(prep.batch.items()))
    fn, hit = _COHORT_FNS.callable(prep.program, mesh, donate, shapes)
    if registry is not None:
        registry.counter("cohort_fn_hits" if hit else "cohort_fn_misses",
                         "cohort dispatches that reused / traced their "
                         "program").inc()
    out, e = shard_lib.dispatch_sharded(fn, prep.batch, mesh)
    return out, e, hit


@dataclasses.dataclass
class CohortPhases:
    """A cohort decomposed for checkpointed (blocked) execution.

    ``jax.vmap(init_one)(batch)`` yields the cohort's initial engine
    states; ``jax.vmap(block_one(n, offs))(state, batch)`` advances every
    experiment ``n`` rounds and returns that block's history slice.
    Chaining blocks reproduces :class:`PreparedCohort`'s whole-scan
    output bit for bit (``lax.scan`` carries no cross-iteration compiler
    state), which is what makes mid-cohort checkpoints safe to resume.
    """

    cohort: Cohort
    batch: Dict[str, jnp.ndarray]
    init_one: Any        # batch slice -> RoundState
    block_one: Any       # (length, eval_offsets) -> f(state, slice)


def prepare_cohort_phases(cohort: Cohort, *, do_eval: bool = True,
                          eval_data=None) -> CohortPhases:
    """Host-side phase for blocked execution (same prep as
    :func:`prepare_cohort`; the computation is split at scan
    boundaries)."""
    uniform, varying = _split_scalars(cohort.cells)
    ctx = _cohort_context(cohort, uniform, tuple(varying), do_eval=do_eval,
                          eval_data=eval_data)
    static = cohort_static_hash(cohort)

    def init_one(batch):
        obs_trace.event("cohort.trace", cat="sweep", static=static)
        X, Y, mask, k_i, wmask, _ = ctx.data_of(batch)
        return scan_experiment_init(ctx.task, X, Y, mask, k_i,
                                    ctx.cfg_of(batch), batch["key"],
                                    wmask=wmask)

    def block_one(length: int, eval_offsets: Tuple[int, ...]):
        def f(state, batch):
            obs_trace.event("cohort.trace", cat="sweep", static=static)
            X, Y, mask, k_i, wmask, eval_xy = ctx.data_of(batch)
            return scan_experiment_block(ctx.task, X, Y, mask, k_i,
                                         ctx.cfg_of(batch), state, length,
                                         eval_offsets=eval_offsets,
                                         eval_xy=eval_xy, wmask=wmask)
        return f

    return CohortPhases(cohort=cohort, batch=_cohort_batch(cohort, varying),
                        init_one=init_one,
                        block_one=block_one)


def cohort_signature(cohort: Cohort,
                     extra: Optional[Dict[str, Any]] = None) -> str:
    """Content id of a cohort's pending work: the sorted hashes of its
    cells (plus the run-level cache extras).  Names checkpoint
    directories, work-stealing claims, and quarantine records — any two
    hosts that would compute the same cells agree on it."""
    hs = sorted(store_lib.cell_hash(c, extra) for c in cohort.cells)
    return hashlib.sha256("|".join(hs).encode()).hexdigest()[:16]


def cohort_static_hash(cohort: Cohort) -> str:
    """Stable id of a cohort's STATIC key (its compiled structure) — the
    key under which measured walls are persisted (``store.CostBook``).
    Cell-independent: an 8-seed cohort and a 64-seed cohort of the same
    structure share it (costs normalize per cell)."""
    import json
    doc = json.dumps(store_lib.jsonable(cohort.static), sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def run_cohort_blocks(cohort: Cohort, *, every: int, ckpt_dir: str,
                      resume: bool = False, do_eval: bool = True,
                      tail: int = 10, eval_data=None,
                      verbose: bool = False) -> List[Dict[str, Any]]:
    """Execute one cohort in checkpointed round blocks.

    Rounds run ``every`` at a time; after each block the engine state
    (the scan carry) and the accumulated histories land in ``ckpt_dir``
    via ``repro.checkpoint.store`` (atomic, ``keep=1``).  With
    ``resume=True`` a matching checkpoint short-circuits the completed
    blocks — the resumed run is byte-identical to an uninterrupted one.
    The caller owns ``ckpt_dir`` cleanup (delete AFTER results are
    persisted, so a crash in the window costs recompute, not
    correctness).

    Runs unsharded (single jit per block shape); mesh-sharded cohorts
    use the whole-scan path.

    When a flight recorder is installed (``obs.flight``), each block's
    jitted function carries an ``io_callback`` tap streaming round-level
    signals into the recorder, and the recorder's divergence sentinel is
    probed between blocks — a trip deletes the checkpoint directory
    (the carry is poisoned; it must not resume) and raises the
    non-retryable :class:`~repro.obs.flight.CohortDiverged`.  With no
    recorder the built functions are the exact untapped computation.
    """
    from repro.checkpoint import store as ckpt
    from repro.obs import flight as flight_lib
    from repro.runtime import faults

    if every <= 0:
        raise ValueError(f"checkpoint interval must be positive: {every}")
    phases = prepare_cohort_phases(cohort, do_eval=do_eval,
                                   eval_data=eval_data)
    rounds = int(cohort.static["rounds"])
    eval_every = int(cohort.static["eval_every"])
    sig = cohort_signature(cohort, {"eval": do_eval, "tail": tail})

    state = jax.jit(jax.vmap(phases.init_one))(phases.batch)
    hist: Dict[str, np.ndarray] = {}
    r_done = 0
    restored = False
    if resume:
        step = ckpt.latest_step(ckpt_dir)
        if step is not None:
            try:
                cand, extra = ckpt.restore(ckpt_dir, state, step)
            except Exception as e:        # corrupt/alien checkpoint: redo
                print(f"# sweep: unusable checkpoint under {ckpt_dir} "
                      f"({type(e).__name__}: {e}); restarting cohort",
                      file=sys.stderr)
            else:
                if extra.get("sig") == sig:
                    state = cand
                    hist = ckpt.load_arrays(ckpt_dir, step)
                    r_done = int(extra["r_done"])
                    restored = True
                    if verbose:
                        print(f"# cohort resume: {r_done}/{rounds} rounds "
                              f"from checkpoint", file=sys.stderr)
    if not restored:
        # a stale dir (older spec, mismatched signature, or a fresh
        # non-resume start) must go: ``save(keep=1)`` keeps the HIGHEST
        # step, and a leftover later step would shadow this run's saves
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    flight_rec = flight_lib.installed()
    tok = (flight_rec.register(sig, rounds=rounds, cells=len(cohort),
                               r_done=r_done)
           if flight_rec is not None else None)

    fns: Dict[Tuple, Any] = {}   # (length, offsets) -> compiled block
    while r_done < rounds:
        n = min(every, rounds - r_done)
        offs = tuple(j for j in range(n)
                     if (r_done + j) % eval_every == 0)
        fn_key = (n, offs)
        if fn_key not in fns:
            base = jax.vmap(phases.block_one(n, offs))
            fns[fn_key] = jax.jit(flight_lib.wrap_block(base)
                                  if tok is not None else base)
        if tok is None:
            state, out = jax.block_until_ready(fns[fn_key](state,
                                                           phases.batch))
        else:
            # token + absolute round index enter as traced scalars so one
            # compile per (length, offsets) serves every block and cohort
            state, out = jax.block_until_ready(
                fns[fn_key](state, phases.batch, jnp.int32(tok),
                            jnp.int32(r_done + n)))
        out = {k: np.asarray(v) for k, v in out.items()}
        hist = {k: (np.concatenate([hist[k], out[k]], axis=1)
                    if k in hist else out[k]) for k in out}
        r_done += n
        if tok is not None:
            flight_lib.barrier()        # the block's tap has landed
            err = flight_rec.check(tok)
            if err is not None:
                # poisoned carry: a resume from this dir would diverge
                # again, and the healing re-run must start clean
                shutil.rmtree(ckpt_dir, ignore_errors=True)
                obs_trace.event("cohort.diverged", sig=sig,
                                round=err.round, reason=err.reason,
                                predicate=err.predicate)
                raise err
            obs_trace.event("flight.block", cat="flight", sig=sig,
                            r_done=r_done, rounds=rounds)
        if faults.tripped("nan_at_block"):
            state = state._replace(
                flat=jnp.full_like(state.flat, jnp.nan))
        # checkpoint every boundary incl. the last: a crash between the
        # final block and the store write then resumes from here instead
        # of recomputing the whole cohort
        ckpt.save(ckpt_dir, r_done, state,
                  extra={"sig": sig, "r_done": r_done}, keep=1,
                  arrays=hist)
        obs_trace.event("cohort.checkpoint", sig=sig, r_done=r_done,
                        rounds=rounds)
        faults.fire("crash_after_block")

    if tok is not None:
        flight_rec.finish(tok)
    final = dict(hist)
    final["flat"] = np.asarray(state.flat)
    return finalize_cohort(cohort, final, tail=tail)


def finalize_cohort(cohort: Cohort, out: Dict[str, np.ndarray], *,
                    tail: int = 10) -> List[Dict[str, Any]]:
    """Host-side phase: per-cell result dicts from the cohort's output
    arrays (already fetched to host memory)."""
    results = []
    for e, cell in enumerate(cohort.cells):
        history = {k: out[k][e].tolist() for k in out if k != "flat"}
        metrics: Dict[str, float] = {
            "selected_mean": float(np.mean(out["selected"][e])),
            "b_mean": float(np.mean(out["b"][e])),
        }
        for k in out:
            if k in ("flat", "selected", "b"):
                continue
            h = out[k][e]
            metrics[f"{k}_final"] = float(h[-1])
            metrics[f"{k}_tail"] = float(np.mean(h[-tail:]))
        results.append({"cell": cell, "metrics": metrics,
                        "history": history, "flat": out["flat"][e]})
    return results


def run_cohort(cohort: Cohort, *, do_eval: bool = True, tail: int = 10,
               mesh=None, eval_data=None, order: int = 0, registry=None
               ) -> List[Dict[str, Any]]:
    """Execute one cohort as a single vmapped (and mesh-sharded) program.

    Returns one result dict per cell (cohort order): ``cell``,
    ``metrics`` (scalar summaries), ``history`` (per-round traces) and
    ``flat`` (final parameters, in-memory only — the store persists
    metrics + history).  ``eval_data`` overrides the task's own test
    split (e.g. Fig. 4's fixed held-out set shared across U).

    The three phases are the async runtime's, traced under its span
    names: ``cohort.prepare`` (the batch, and on a cache miss the host
    data), ``cohort.dispatch`` (enqueue of the cached jitted program; on
    a miss also trace, lower, compile or cache read; ``hit`` says which)
    and ``cohort.resolve`` (wait for the device, fetch, finalize).
    ``order`` is the cohort's position in its plan, which the spans
    carry as ``cohort``; ``registry`` counts the program cache's hits
    and misses (:func:`dispatch_cohort`).
    """
    with obs_trace.span("cohort.prepare", cat="sweep", cohort=order,
                        cells=len(cohort)):
        prep = prepare_cohort(cohort, do_eval=do_eval, eval_data=eval_data)
    with obs_trace.span("cohort.dispatch", cat="sweep", cohort=order,
                        cells=len(cohort)) as args:
        out, e, args["hit"] = dispatch_cohort(prep, mesh,
                                              registry=registry)
    with obs_trace.span("cohort.resolve", cat="sweep", cohort=order,
                        cells=len(cohort)):
        host = shard_lib.resolve(out, e)
        host = {k: np.asarray(v) for k, v in host.items()}
        return finalize_cohort(cohort, host, tail=tail)


def spec_cache_key(spec: SweepSpec) -> Dict[str, Any]:
    """The run-level store-identity extras for ``spec`` — shared by the
    serial path, the async runtime, and multi-host merging (all three
    must agree or caches would silently miss across execution modes)."""
    return {"eval": spec.eval, "tail": spec.tail}


def ckpt_dir_for(store_root: str, sig: str) -> str:
    """Checkpoint directory for a cohort signature (shared layout between
    the serial path, the async runtime, and multi-host work stealing)."""
    return os.path.join(store_root, ".runtime", "ckpt", sig)


def runtime_gc(store_root: str) -> None:
    """Drop the transient ``.runtime`` tree when it is empty of work —
    called after a fully successful sweep so a clean store stays
    byte-comparable against any other clean run of the same grid."""
    root = os.path.join(store_root, ".runtime")
    for sub in ("ckpt", "claims"):
        p = os.path.join(root, sub)
        if os.path.isdir(p) and not os.listdir(p):
            shutil.rmtree(p, ignore_errors=True)
    if os.path.isdir(root) and not os.listdir(root):
        shutil.rmtree(root, ignore_errors=True)


def run_spec(spec: SweepSpec, *, store: Optional[store_lib.SweepStore] = None,
             mesh=None, eval_data=None, verbose: bool = False,
             jobs: Union[int, str] = 1,
             dispatch_ahead: Optional[int] = None,
             resume: bool = False, checkpoint_every: Optional[int] = None,
             max_retries: int = 0, retry_backoff: float = 0.5,
             quarantine: bool = False, registry=None
             ) -> List[Optional[Dict[str, Any]]]:
    """Run a whole grid: cache lookups, cohort batching, store writes.

    Returns one result per cell in grid order.  Cached cells are served
    from ``store`` without executing; only the misses are regrouped into
    cohorts and run.  The cache identity covers the spec's evaluation
    settings (``eval``, ``tail``) as well as the cell, so e.g. a
    ``--no-eval`` run never satisfies a later metrics-wanting run.

    ``jobs >= 2`` routes the pending cohorts through the async runtime
    (``repro.runtime.scheduler``): cohorts dispatch concurrently ordered
    by cost estimate, with up to ``jobs + dispatch_ahead`` cohorts in
    flight and store writes drained by a background writer thread.
    ``jobs="auto"`` sizes the pool from the store's CostBook measured
    walls and the host's CPU count (``repro.serve.admission.auto_jobs``).
    Results are INVARIANT to scheduling — the async path runs the exact
    same prepared computations per cohort, so every cell's result (and
    store artifact) is identical to the serial ``jobs=1`` run.

    Fault tolerance (see ``docs/runtime.md``):

    * ``checkpoint_every=R`` executes cohorts in R-round blocks with the
      scan carry checkpointed under ``<store>/.runtime/ckpt/`` after
      every block (requires ``store``; incompatible with ``mesh``).
    * ``resume=True`` sweeps orphaned store tmp files and picks partial
      cohorts up from their last block boundary.  Results are
      byte-identical to an uninterrupted run.
    * ``max_retries=N`` re-runs a failed cohort up to N times with
      exponential backoff (``retry_backoff * 2**attempt`` seconds).
    * ``quarantine=True`` converts a cohort that exhausts its retries
      into a structured ``<store>/failed/<sig>.json`` record — its
      cells' results stay ``None`` and the REST of the grid completes —
      instead of aborting the sweep.  Defaults keep the historical
      fail-fast behavior.

    ``registry`` (an ``repro.obs.metrics.Registry``) collects run
    metrics — cells/hits counters and, on the async path, the engine's
    counter/histogram series — through the SAME collectors the service
    daemon renders at ``/metrics`` (the CLI's ``--metrics-out`` dumps
    this registry's snapshot).
    """
    if jobs == "auto":
        # sized from measured walls, not from the grid: the book reflects
        # what this store's cohorts actually cost on this class of host
        from repro.serve import admission as admission_lib
        jobs = admission_lib.auto_jobs(
            store_lib.CostBook(store.root) if store is not None else None)
        if verbose:
            print(f"# sweep: auto-tuned jobs={jobs}", file=sys.stderr)
    if store is not None and eval_data is not None:
        # an eval_data override changes every metric without changing any
        # cell, so cached entries would be poisoned for ordinary runs
        raise ValueError("store and eval_data are mutually exclusive; "
                         "run eval-override sweeps uncached")
    if checkpoint_every is not None:
        if store is None:
            raise ValueError("checkpoint_every requires a store (the "
                             "checkpoints live under its root)")
        if mesh is not None:
            raise ValueError("checkpoint_every is incompatible with an "
                             "explicit mesh: blocked cohorts run "
                             "unsharded")
    if (resume or quarantine) and store is None:
        raise ValueError("resume/quarantine require a store")
    if resume:
        # exclusive access is the --resume contract: any tmp file is
        # debris from the dead run, not a live writer's staging file
        store.gc_tmp(0.0)

    cache_key = spec_cache_key(spec)
    cell_list = cells(spec)
    results: List[Optional[Dict[str, Any]]] = [None] * len(cell_list)
    pending_cells, pending_idx = [], []
    for i, cell in enumerate(cell_list):
        cached = store.get(cell, cache_key) if store is not None else None
        if cached is not None:
            # the store round-trips the cell through JSON; hand callers
            # back the original dict so result_by matching keeps working
            results[i] = {**cached, "cell": cell}
        else:
            pending_cells.append(cell)
            pending_idx.append(i)
    if verbose and store is not None:
        hits = len(cell_list) - len(pending_cells)
        print(f"# sweep: {len(cell_list)} cells, {hits} cache hits",
              file=sys.stderr)
    pending = cohorts(pending_cells, pending_idx)
    if registry is not None:
        registry.counter("cells_requested").inc(len(cell_list))
        registry.counter("cells_hit").inc(
            len(cell_list) - len(pending_cells))
        registry.counter("cells_computed").inc(len(pending_cells))
    obs_trace.event("sweep.submit", cat="sweep", cells=len(cell_list),
                    hits=len(cell_list) - len(pending_cells),
                    cohorts=len(pending))
    costs = (store_lib.CostBook(store.root) if store is not None else None)

    def settle(cohort: Cohort, outs: List[Dict[str, Any]]) -> None:
        for idx, res in zip(cohort.indices, outs):
            results[idx] = res
            if store is not None:
                store.put(res["cell"], res, cache_key)
        if checkpoint_every is not None:
            # results are durable; the cohort's checkpoints are now dead
            sig = cohort_signature(cohort, cache_key)
            shutil.rmtree(ckpt_dir_for(store.root, sig),
                          ignore_errors=True)

    if jobs > 1:
        from repro.runtime import scheduler as sched_lib
        sched_lib.run_cohorts(pending, sink=settle, jobs=jobs,
                              dispatch_ahead=dispatch_ahead,
                              do_eval=spec.eval, tail=spec.tail,
                              mesh=mesh, eval_data=eval_data,
                              verbose=verbose, costs=costs,
                              store_root=(store.root if store is not None
                                          else None),
                              resume=resume,
                              checkpoint_every=checkpoint_every,
                              max_retries=max_retries,
                              retry_backoff=retry_backoff,
                              quarantine=quarantine,
                              registry=registry)
        if store is not None:
            runtime_gc(store.root)
        return results

    from repro.runtime import faults, resilience
    policy = resilience.RetryPolicy(max_retries=max_retries,
                                    backoff_s=retry_backoff)
    qclear = (resilience.QuarantineLog(store.root)
              if store is not None else None)
    qlog = qclear if quarantine else None
    for order, cohort in enumerate(pending, start=1):
        if verbose:
            u_vals = sorted({c["U"] for c in cohort.cells})
            print(f"# cohort x{len(cohort)}"
                  f"{' (ragged)' if cohort.ragged else ''}: "
                  f"policy={cohort.static['policy']} "
                  f"channel={cohort.static['channel']} "
                  f"U={u_vals if len(u_vals) > 1 else u_vals[0]} "
                  f"rounds={cohort.static['rounds']}",
                  file=sys.stderr)

        def execute(attempt: int) -> List[Dict[str, Any]]:
            faults.fire("kill_at_cohort", cohort=order)
            faults.fire("fail_cohort", cohort=order)
            faults.fire("flaky_cohort", cohort=order)
            if checkpoint_every is not None:
                sig = cohort_signature(cohort, cache_key)
                return run_cohort_blocks(
                    cohort, every=checkpoint_every,
                    ckpt_dir=ckpt_dir_for(store.root, sig),
                    resume=resume or attempt > 0, do_eval=spec.eval,
                    tail=spec.tail, eval_data=eval_data, verbose=verbose)
            return run_cohort(cohort, do_eval=spec.eval, tail=spec.tail,
                              mesh=mesh, eval_data=eval_data,
                              order=order - 1, registry=registry)

        # schedule-time prediction (measured walls only): graded against
        # the realized wall below, same contract as the async scheduler
        predicted = None
        if costs is not None:
            w = costs.per_cell_wall(cohort_static_hash(cohort))
            if w is not None:
                predicted = w * len(cohort)
        t0 = time.time()
        with obs_trace.span("cohort.run", cat="sweep", cohort=order - 1,
                            cells=len(cohort)):
            outs = resilience.run_with_retry(
                execute, policy=policy, quarantine=qlog, cohort=cohort,
                cache_key=cache_key,
                label=f"cohort {order}/{len(pending)}",
                verbose=verbose, clear_log=qclear)
        if outs is None:
            continue                       # quarantined; rest of the grid runs
        wall = time.time() - t0
        if registry is not None:
            registry.histogram(
                "engine_cohort_wall_seconds",
                "dispatch-start to resolve-end wall per cohort"
            ).observe(wall)
        if predicted is not None and predicted > 0 and wall > 0:
            ratio = wall / predicted
            if ratio > 2.0 or ratio < 0.5:
                obs_trace.event("cost.mispredict", cohort=order - 1,
                                predicted_s=predicted, measured_s=wall,
                                ratio=ratio)
                if registry is not None:
                    registry.counter("engine_costs_mispredicted").inc()
        if costs is not None:
            costs.record(cohort_static_hash(cohort), wall_s=wall,
                         cells=len(cohort), predicted_s=predicted)
        settle(cohort, outs)
    if store is not None:
        runtime_gc(store.root)
    return results


def result_by(results: List[Dict[str, Any]],
              **match: Any) -> Dict[str, Any]:
    """The unique result whose cell matches every ``match`` item."""
    found = [r for r in results
             if all(r["cell"].get(k) == v for k, v in match.items())]
    if len(found) != 1:
        raise ValueError(f"{len(found)} results match {match}")
    return found[0]
