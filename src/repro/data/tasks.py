"""Named experiment tasks: (TaskModel, worker datasets, test set) builders.

The paper's two Sec. VI workloads — the 1-neuron linear regression and the
784-64-10 MLP — were previously assembled ad hoc inside
``benchmarks/common.py``.  The sweep engine (``repro.sweep``) needs the
same builders from library code (benchmarks must stay importable without
``src`` layering violations), so they live here and ``benchmarks.common``
delegates.

A task builder is registered under a name and called as

    build_task_data(name, U=20, k_bar=30, data_seed=0)
      -> (TaskModel, workers, (x_test, y_test))

where ``workers`` is the ``FLTrainer`` list of (x_i, y_i) per-worker
datasets.  ``data_seed`` drives both the per-worker sample counts
K_i ~ round(U[K̄-5, K̄+5]) and the dataset draw, exactly as the fig
benchmarks always have.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from repro.data import partition, synthetic
from repro.fl.models import TaskModel, linreg_model, mlp_model, ridge_model

TaskData = Tuple[TaskModel, List[Tuple[Any, Any]], Tuple[Any, Any]]

_TASK_REGISTRY: Dict[str, Callable[..., TaskData]] = {}

# Model dimension D per task — the cost-estimate input the async runtime
# scheduler uses to order cohort dispatch (cells x rounds x U_max x D)
# WITHOUT building any task data.  Unknown tasks fall back to 1: ordering
# degrades gracefully, correctness never depends on it.
_DIM_HINTS: Dict[str, int] = {"linreg": 3, "ridge": 8, "mlp": 50890,
                               "moonlight_lm": 568_484_352}


def dim_hint(name: Any, default: int = 1) -> int:
    """Approximate flattened parameter count for a registered task."""
    return _DIM_HINTS.get(name, default) if isinstance(name, str) \
        else default


def register_task(name: str):
    """Register a task-data builder under ``name``."""
    def deco(fn):
        _TASK_REGISTRY[name] = fn
        return fn
    return deco


def task_names() -> Tuple[str, ...]:
    return tuple(sorted(_TASK_REGISTRY))


def build_task_data(name: str, U: int = 20, k_bar: int = 30,
                    data_seed: int = 0, **kwargs) -> TaskData:
    try:
        builder = _TASK_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown task {name!r}; registered: {task_names()}") from None
    return builder(U=U, k_bar=k_bar, data_seed=data_seed, **kwargs)


@register_task("linreg")
def _linreg(U: int = 20, k_bar: int = 30, data_seed: int = 0,
            n_test: int = 512) -> TaskData:
    """Paper Sec. VI-A: y = -2x + 1 + 0.4n, U workers, K̄ ± 5 samples."""
    counts = partition.sample_counts(U, k_bar, seed=data_seed)
    x, y = synthetic.linreg(int(np.sum(counts)) + n_test, seed=data_seed)
    workers = partition.partition(x, y, counts, seed=data_seed)
    return linreg_model(), workers, (x[-n_test:], y[-n_test:])


@register_task("ridge")
def _ridge(U: int = 10, k_bar: int = 40, data_seed: int = 0,
           d: int = 8, lam: float = 0.05) -> TaskData:
    """Theory-check workload: ridge least squares with uniform K_i = k_bar
    per worker, so L / mu / F(w*) are exactly computable from the global
    (X, y) — which is returned as the "test" split on purpose: evaluating
    ``fval`` against it reads the global objective F(w_t) per round.
    """
    rng = np.random.default_rng(data_seed)
    n = U * k_bar
    X = rng.normal(size=(n, d)) / np.sqrt(d)
    w_true = rng.normal(size=(d,))
    y = X @ w_true + 0.1 * rng.normal(size=(n,))
    workers = [(X[i * k_bar:(i + 1) * k_bar], y[i * k_bar:(i + 1) * k_bar])
               for i in range(U)]
    return ridge_model(d, lam), workers, (X, y)


@register_task("mlp")
def _mlp(U: int = 20, k_bar: int = 40, data_seed: int = 0,
         n_test: int = 2000) -> TaskData:
    """Paper Sec. VI-B: 784-64-10 MLP over the synthetic cluster dataset."""
    counts = partition.sample_counts(U, k_bar, seed=data_seed)
    x, y = synthetic.mnist_like(int(np.sum(counts)) + n_test,
                                seed=data_seed)
    workers = partition.partition(x[:-n_test], y[:-n_test], counts,
                                  seed=data_seed)
    return mlp_model(), workers, (x[-n_test:], y[-n_test:])


@register_task("moonlight_lm")
def _moonlight_lm(U: int = 20, k_bar: int = 40, data_seed: int = 0,
                  n_test: int = 8, seq_len: int = 1024,
                  **shape: Any) -> TaskData:
    """Moonlight-16B-A3B's one-chip share (``fl/lm_models.py``; D =
    568,484,352) as the local model: worker i holds K_i ~ round(U[K̄-5,
    K̄+5]) sequences of ``seq_len + 1`` ids from a fixed sparse Markov
    source over the vocabulary slice, as (inputs, next ids) pairs.
    ``shape`` overrides ``LMShape`` widths (small models in tests).
    Runs only in the worker-sharded engine (``FLConfig.worker_sharding``).
    """
    from repro.fl import lm_models
    from repro.obs import trace

    lm_shape = lm_models.LMShape(**shape)
    D = lm_models.param_count(lm_shape)
    with trace.span("task.build", cat="task", task="moonlight_lm", D=D,
                    bytes=4 * D):
        counts = partition.sample_counts(U, k_bar, seed=data_seed)
        tok = synthetic.markov_tokens(int(np.sum(counts)) + n_test,
                                      seq_len + 1, lm_shape.vocab,
                                      seed=data_seed)
        x, y = tok[:, :-1], tok[:, 1:]
        ends = np.cumsum(counts)
        workers = [(x[e - k:e], y[e - k:e]) for k, e in zip(counts, ends)]
        return (lm_models.moonlight_model(lm_shape), workers,
                (x[-n_test:], y[-n_test:]))
