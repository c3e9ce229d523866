"""Dense (paper-faithful) FL-over-the-air trainer — Algorithm 1 (INFLOTA).

Simulates the full wireless loop for U workers with a (U, D) matrix of
local parameter vectors: local GD/SGD -> channel draw -> policy (b, beta)
-> analog-aggregation transmission (with clipping) -> PS post-processing ->
next round.  This is the path used to validate every Sec. VI figure.

The per-round computation is one fused, jit/scan-compatible
``round_step`` built by ``repro.fl.engine``: vmap-batched local updates
over K_max-padded worker data, a rank-1 (scalar-per-worker) channel end
to end, and a backend switch between the pure-jnp reference and the
single-VMEM-pass Pallas kernel (``FLConfig.backend="pallas"``; the legacy
``use_kernels=True`` is deprecated).  Scenarios are pluggable:
``FLConfig.channel_model`` takes any ``repro.core.channel.ChannelModel``
(iid / time-correlated / heterogeneous / imperfect-CSI) and
``FLConfig.policy`` any ``repro.core.selection.RoundPolicy`` — by
registry name or instance.  With ``FLConfig.scan=True`` the whole
training run is one ``jax.lax.scan`` (small-D workloads); otherwise a
Python loop drives the same jitted step so metrics can be evaluated per
round.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

# Backend / FLConfig / state types live in engine.py; re-exported here for
# the established public import path (tests, examples, benchmarks).
from repro.fl.engine import (Backend, Engine, FLConfig, RoundState,
                             build_engine, init_state)
from repro.fl.models import TaskModel

__all__ = ["Backend", "FLConfig", "FLTrainer", "pad_workers",
           "scan_experiment", "scan_experiment_init",
           "scan_experiment_block"]


def _pad_axis0(a: jnp.ndarray, k_max: int) -> jnp.ndarray:
    pad = [(0, k_max - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def pad_workers(worker_data: List[Tuple[Any, Any]],
                k_max: Optional[int] = None):
    """Worker datasets -> uniform-shape (X, Y, mask, k_i) engine batch.

    Pads every worker to ``k_max`` (default: the fleet-wide max) along
    axis 0 with sample masks.  Shared by ``FLTrainer`` and the sweep
    engine so both feed the round engine bit-identical arrays; ragged
    cohorts pass an explicit cohort-wide ``k_max`` so cells with
    different sample counts share one compiled shape (zero-padding with
    a zero mask is bit-exact — padded samples contribute 0 to the
    mask-weighted mean loss and its gradient).
    """
    sizes = [np.asarray(x).shape[0] for x, _ in worker_data]
    k_i = jnp.asarray(sizes, jnp.float32)
    if k_max is None:
        k_max = max(sizes)
    elif k_max < max(sizes):
        raise ValueError(
            f"k_max={k_max} below the largest worker ({max(sizes)})")
    X = jnp.stack([_pad_axis0(jnp.asarray(x), k_max)
                   for x, _ in worker_data])
    Y = jnp.stack([_pad_axis0(jnp.asarray(y), k_max)
                   for _, y in worker_data])
    mask = jnp.asarray(
        np.arange(k_max)[None, :] < np.asarray(sizes)[:, None],
        jnp.float32)
    return X, Y, mask, k_i


def scan_experiment(task: TaskModel, X, Y, mask, k_i, cfg: FLConfig,
                    key, eval_xy: Optional[Tuple[Any, Any]] = None,
                    wmask=None) -> Dict[str, jax.Array]:
    """One full ``scan=True`` training run as a pure traced function.

    This is the single source of truth for the scan path: ``FLTrainer``
    jits it directly, and the sweep engine (``repro.sweep``) lifts it over
    a leading experiment axis with ``jax.vmap`` — ``key``, any config
    scalars the sweep varies (``lr``, ``sigma2``, ``p_max``, ``eps``,
    ``rho``, ``L``) and even the worker data block (``X``/``Y``/``mask``/
    ``k_i`` plus the ragged-cohort worker mask ``wmask``) may be traced,
    so a whole grid of runs compiles once and executes as one
    device-resident computation.

    Returns a dict of arrays: ``flat`` (final parameters, flattened),
    ``selected`` / ``b`` / ``a_t`` / ``b_t`` per-round stats (rounds,) —
    the latter two are the realized Lemma-1 terms, letting callers
    accumulate the paper's convergence bound (``conv.gap_recursion``)
    cohort-wide — and, when ``eval_xy`` is given, one
    (rounds / eval_every,) history per task metric.
    """
    kinit, kround = jax.random.split(key)
    params = task.init(kinit)
    engine = build_engine(task, X, Y, mask, k_i, cfg, params, wmask=wmask)
    flat0, _ = ravel_pytree(params)
    state = engine.init(flat0, kround)
    collect = eval_xy is not None

    def body(s, _):
        s2, stats = engine.step(s, None)
        return s2, (stats, s2.flat if collect else None)

    state, (stats, flats) = jax.lax.scan(body, state, None,
                                         length=cfg.rounds)
    out = {"flat": state.flat, "selected": stats.selected,
           "b": stats.b_mean, "a_t": stats.a_t, "b_t": stats.b_t,
           "eta": stats.eta, "snr": stats.snr}
    if collect:
        ex, ey = (jnp.asarray(eval_xy[0]), jnp.asarray(eval_xy[1]))
        idx = jnp.arange(0, cfg.rounds, cfg.eval_every)
        with jax.named_scope("fl.eval"):
            ms = jax.vmap(lambda f: task.metrics(engine.unravel(f), ex,
                                                 ey))(flats[idx])
        out.update(ms)
    return out


def scan_experiment_init(task: TaskModel, X, Y, mask, k_i, cfg: FLConfig,
                         key, wmask=None) -> RoundState:
    """The pre-scan half of ``scan_experiment``: params init + engine init.

    Splitting ``scan_experiment`` into init + round blocks is what lets
    long cohorts checkpoint at scan boundaries: chaining
    ``scan_experiment_block`` calls from this state is bit-identical to
    one full-length scan (``lax.scan`` carries no cross-iteration
    compiler state), so a resumed run reproduces the uninterrupted one
    byte for byte.
    """
    kinit, kround = jax.random.split(key)
    params = task.init(kinit)
    engine = build_engine(task, X, Y, mask, k_i, cfg, params, wmask=wmask)
    flat0, _ = ravel_pytree(params)
    return engine.init(flat0, kround)


def scan_experiment_block(task: TaskModel, X, Y, mask, k_i, cfg: FLConfig,
                          state: RoundState, length: int,
                          eval_offsets: Tuple[int, ...] = (),
                          eval_xy: Optional[Tuple[Any, Any]] = None,
                          wmask=None
                          ) -> Tuple[RoundState, Dict[str, jax.Array]]:
    """``length`` rounds of ``scan_experiment`` from a carried state.

    ``eval_offsets`` are the BLOCK-LOCAL round indices at which to
    evaluate metrics (the caller maps the global ``t % eval_every == 0``
    grid into each block), so concatenating per-block histories
    reproduces the full-scan histories exactly.  Returns the carried
    state plus the block's slice of every history key — ``flat`` is not
    included; the final parameters live in the returned state.
    """
    # params values are irrelevant here (only the pytree structure feeds
    # the engine's unravel); a constant key keeps the template unbatched
    # under the sweep engine's vmap over experiments.
    params = task.init(jax.random.PRNGKey(0))
    engine = build_engine(task, X, Y, mask, k_i, cfg, params, wmask=wmask)
    collect = eval_xy is not None

    def body(s, _):
        s2, stats = engine.step(s, None)
        return s2, (stats, s2.flat if collect else None)

    state, (stats, flats) = jax.lax.scan(body, state, None, length=length)
    out = {"selected": stats.selected, "b": stats.b_mean,
           "a_t": stats.a_t, "b_t": stats.b_t,
           "eta": stats.eta, "snr": stats.snr}
    if collect:
        ex, ey = (jnp.asarray(eval_xy[0]), jnp.asarray(eval_xy[1]))
        idx = jnp.asarray(np.asarray(eval_offsets, np.int32))
        # vmap over a zero-length axis is fine: a block with no eval
        # rounds still emits every metric key, with a (0,) history
        with jax.named_scope("fl.eval"):
            ms = jax.vmap(lambda f: task.metrics(engine.unravel(f), ex,
                                                 ey))(flats[idx])
        out.update(ms)
    return state, out


class FLTrainer:
    """Orchestrates Algorithm 1 over a list of worker datasets."""

    def __init__(self, task: TaskModel, worker_data: List[Tuple[Any, Any]],
                 cfg: FLConfig):
        self.task = task
        self.cfg = cfg
        self.U = len(worker_data)
        # uniform-shape batch across workers: pad to K_max + sample masks,
        # so the engine runs ONE vmapped local-update dispatch per round
        self.X, self.Y, self.mask, self.k_i = pad_workers(worker_data)

    # ---------------------------------------------------------------- run
    def run(self, key=None, eval_data: Optional[Tuple[Any, Any]] = None
            ) -> Dict[str, Any]:
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(cfg.seed)
        history: Dict[str, list] = {"round": list(range(cfg.rounds)),
                                    "selected": [], "b": []}
        if cfg.scan:
            return self._run_scan(key, history, eval_data)
        kinit, kround = jax.random.split(key)
        params = self.task.init(kinit)
        engine = build_engine(self.task, self.X, self.Y, self.mask,
                              self.k_i, cfg, params)
        flat, _ = ravel_pytree(params)
        state = engine.init(flat, kround)
        state, history = self._run_loop(engine, state, history, eval_data)
        history["params"] = engine.unravel(state.flat)
        return history

    # one scan over all rounds: no host round-trips at all.  The whole run
    # is the shared ``scan_experiment`` pure function (also the sweep
    # engine's unit of vmapping); compile time is measured separately from
    # execution so reported wall clocks are honest.
    def _run_scan(self, key, history, eval_data):
        cfg = self.cfg

        def run_fn(k):
            return scan_experiment(self.task, self.X, self.Y, self.mask,
                                   self.k_i, cfg, k, eval_xy=eval_data)

        t0 = time.time()
        compiled = jax.jit(run_fn).lower(key).compile()
        history["compile_s"] = time.time() - t0
        self.compiled = compiled        # the executable run: its HLO, costs
        out = jax.block_until_ready(compiled(key))
        for k, v in out.items():
            if k != "flat":
                history[k] = np.asarray(v).tolist()
        # rebuild the params template (same kinit stream) only to unravel
        kinit, _ = jax.random.split(key)
        _, unravel = ravel_pytree(self.task.init(kinit))
        history["params"] = unravel(out["flat"])
        return history

    # Python loop over the same jitted step: per-round eval on host
    def _run_loop(self, engine: Engine, state: RoundState, history,
                  eval_data):
        cfg = self.cfg
        step = jax.jit(engine.step)
        jit_metrics = jax.jit(self.task.metrics)
        if eval_data is not None:
            ex, ey = (jnp.asarray(eval_data[0]), jnp.asarray(eval_data[1]))
        for t in range(cfg.rounds):
            state, stats = step(state, None)
            history["selected"].append(float(stats.selected))
            history["b"].append(float(stats.b_mean))
            history.setdefault("a_t", []).append(float(stats.a_t))
            history.setdefault("b_t", []).append(float(stats.b_t))
            history.setdefault("eta", []).append(float(stats.eta))
            history.setdefault("snr", []).append(float(stats.snr))
            if eval_data is not None and t % cfg.eval_every == 0:
                m = jit_metrics(engine.unravel(state.flat), ex, ey)
                for k, v in m.items():
                    history.setdefault(k, []).append(float(v))
        return state, history
