"""Shared helpers for the paper-figure benchmarks."""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data import tasks as tasks_lib
from repro.fl.models import linreg_model, mlp_model
from repro.fl.trainer import FLConfig, FLTrainer

POLICIES = ("perfect", "inflota", "random")

# Paper Sec. VI: U=20, P_max=10 mW, sigma^2=1e-4 mW, h ~ Exp(1).
PAPER_CHANNEL = ChannelConfig(sigma2=1e-4, p_max=10.0)


def device_info() -> Dict[str, object]:
    """The device a result was measured on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def linreg_workers(U: int = 20, k_bar: int = 30, seed: int = 0):
    _, workers, test = tasks_lib.build_task_data(
        "linreg", U=U, k_bar=k_bar, data_seed=seed)
    return workers, test


def mlp_workers(U: int = 20, k_bar: int = 40, seed: int = 0,
                n_test: int = 2000):
    _, workers, test = tasks_lib.build_task_data(
        "mlp", U=U, k_bar=k_bar, data_seed=seed, n_test=n_test)
    return workers, test


def run_policy(task, workers, test, policy: str, rounds: int,
               lr: float, case: Case, sigma2: float | None = None,
               k_b: int | None = None, seed: int = 0,
               constants: LearningConstants | None = None,
               backend: str = "auto", scan: bool = False,
               channel_model=None) -> Dict:
    """One FLTrainer run; ``channel_model`` is a registry name or a
    ``repro.core.channel.ChannelModel`` instance (None = paper iid).

    ``wall_s`` is honest: the final state is ``block_until_ready``-forced
    before the clock stops.  With ``scan=True`` the trainer additionally
    reports ``compile_s`` (first-call trace+compile overhead) separately,
    so steady-state throughput is ``wall_s - compile_s``.
    """
    chanc = PAPER_CHANNEL if sigma2 is None else ChannelConfig(
        sigma2=sigma2, p_max=PAPER_CHANNEL.p_max)
    cfg = FLConfig(rounds=rounds, lr=lr, policy=policy, case=case,
                   k_b=k_b, channel=chanc, channel_model=channel_model,
                   constants=constants or LearningConstants(
                       sigma2=chanc.sigma2),
                   backend=backend, scan=scan,
                   seed=seed)
    tr = FLTrainer(task, workers, cfg)
    t0 = time.time()
    hist = tr.run(key=jax.random.PRNGKey(seed), eval_data=test)
    jax.block_until_ready(jax.tree.leaves(hist["params"]))
    hist["wall_s"] = time.time() - t0
    return hist


def phase_times(phases: Dict[str, "object"], reps: int = 3,
                warmup: int = 1) -> Dict[str, float]:
    """Median wall seconds for each named phase thunk, honestly separated.

    Each phase is a zero-arg callable returning jax values; the clock
    stops only after ``jax.block_until_ready`` on the result, so kernel
    time, cross-shard reduction/collective time, and end-to-end round
    time can be reported as distinct rows instead of one blended number
    (async dispatch would otherwise attribute a phase's work to whoever
    blocks first).  ``warmup`` calls absorb trace+compile.
    """
    out: Dict[str, float] = {}
    for name, fn in phases.items():
        for _ in range(warmup):
            jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        out[name] = float(np.median(ts))
    return out


def seed_spread_rows(base: dict, metric: str, label: str, name_fmt: str,
                     seeds: int, digits: int = 5) -> List[dict]:
    """Per-policy mean/std of ``metric`` over an N-seed vectorized sweep.

    One ``repro.sweep`` cohort per policy replaces N sequential trainer
    runs; emits ``{label}_mean_{N}seeds`` / ``{label}_std_{N}seeds`` rows
    named by ``name_fmt.format(policy=...)``.
    """
    from repro.sweep import SweepSpec, run_spec
    spec = SweepSpec(axes={"policy": POLICIES,
                           "seed": tuple(range(seeds))}, base=base)
    results = run_spec(spec)
    rows = []
    for policy in POLICIES:
        vals = [r["metrics"][metric] for r in results
                if r["cell"]["policy"] == policy]
        name = name_fmt.format(policy=policy)
        rows += [
            {"name": name, "metric": f"{label}_mean_{seeds}seeds",
             "value": round(float(np.mean(vals)), digits)},
            {"name": name, "metric": f"{label}_std_{seeds}seeds",
             "value": round(float(np.std(vals)), digits)},
        ]
    return rows


def emit(rows: List[dict]) -> None:
    """Print benchmark rows as ``name,metric,value`` CSV lines."""
    for r in rows:
        print(f"{r['name']},{r['metric']},{r['value']}")
