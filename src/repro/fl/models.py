"""Small task models for the paper's Sec. VI experiments.

A model is a triple of pure functions over a parameter pytree:
    init(key) -> params
    loss(params, x, y) -> scalar
    metrics(params, x, y) -> dict

The models' matmuls run at full f32 precision on every backend
(``matmul``).  A TPU otherwise runs an f32 matmul as one bfloat16 pass:
then an ulp of difference in the weights, as between the Pallas and jnp
rounds, can flip the rounding of an input in the next local update, and
runs on the chip could not reproduce runs or stores made on the CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TaskModel:
    init: Callable[[Any], Any]
    loss: Callable[[Any, Any, Any], Any]
    metrics: Callable[[Any, Any, Any], Dict[str, Any]]
    # a model whose (U, D) block of local updates no device holds runs
    # only in the worker-sharded engine (``FLConfig.worker_sharding``)
    sharded_only: bool = False


def matmul(a, b):
    """``a @ b`` at full f32 precision (also in its gradients)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def linreg_model() -> TaskModel:
    """Paper Sec. VI-A: 'two-layer' 1-neuron linear network, MSE (convex)."""

    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": 0.1 * jax.random.normal(k1, (1,)),
                "b1": jnp.zeros((1,)),
                "w2": 1.0 + 0.1 * jax.random.normal(k2, (1,))}

    def predict(p, x):
        return p["w2"] * (p["w1"] * x + p["b1"])

    def loss(p, x, y):
        return jnp.mean((predict(p, x) - y) ** 2)

    def metrics(p, x, y):
        return {"mse": loss(p, x, y)}

    return TaskModel(init=init, loss=loss, metrics=metrics)


def ridge_model(d: int = 8, lam: float = 0.05) -> TaskModel:
    """Ridge-regularized linear least squares with exactly computable
    constants — the workload of ``benchmarks/theory_check.py``:

        F(w) = ||Xw - y||^2 / K + lam ||w||^2

    so L = 2 lambda_max(X^T X / K) + 2 lam, mu = 2 lambda_min + 2 lam and
    F(w*) is closed-form.  ``init`` is the deterministic w_0 = 0 the
    Lemma-1 check starts the bound recursion from; ``metrics`` reports the
    objective value itself (``fval``) so the empirical expected gap
    E[F(w_t) - F*] is directly readable from sweep histories.
    """

    def init(key):
        del key
        return {"w": jnp.zeros((d,))}

    def predict(p, x):
        return matmul(x, p["w"])

    def loss(p, x, y):
        return (jnp.mean((predict(p, x) - y) ** 2)
                + lam * jnp.sum(p["w"] ** 2))

    def metrics(p, x, y):
        return {"fval": loss(p, x, y)}

    return TaskModel(init=init, loss=loss, metrics=metrics)


def mlp_model(d_in: int = 784, hidden: int = 64,
              n_classes: int = 10) -> TaskModel:
    """Paper Sec. VI-B: 784-64-10 MLP, ReLU, cross-entropy (non-convex).

    Total parameters: 784*64 + 64 + 64*10 + 10 = 50890, matching the paper.
    """

    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (d_in, hidden)) * (2.0 / d_in) ** 0.5,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, n_classes)) * (2.0 / hidden) ** 0.5,
            "b2": jnp.zeros((n_classes,)),
        }

    def logits(p, x):
        h = jax.nn.relu(matmul(x, p["w1"]) + p["b1"])
        return matmul(h, p["w2"]) + p["b2"]

    def loss(p, x, y):
        lg = logits(p, x)
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1)
                        - jnp.take_along_axis(lg, y[:, None], axis=1)[:, 0])

    def metrics(p, x, y):
        lg = logits(p, x)
        acc = jnp.mean((jnp.argmax(lg, -1) == y).astype(jnp.float32))
        return {"ce": loss(p, x, y), "accuracy": acc}

    return TaskModel(init=init, loss=loss, metrics=metrics)
