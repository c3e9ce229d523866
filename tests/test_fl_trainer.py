"""Algorithm-1 trainer behaviour: paper Sec. VI comparative claims (fast)."""

import jax
import numpy as np
import pytest

from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data import partition, synthetic
from repro.fl.models import linreg_model, mlp_model
from repro.fl.trainer import FLConfig, FLTrainer

jax.config.update("jax_platform_name", "cpu")


def _workers(U=10, k_bar=25, seed=0):
    counts = partition.sample_counts(U, k_bar, seed=seed)
    x, y = synthetic.linreg(int(np.sum(counts)) + 256, seed=seed)
    return (partition.partition(x, y, counts, seed=seed),
            (x[-256:], y[-256:]))


def _run(policy, rounds=120, sigma2=1e-4, seed=0, backend="jnp"):
    workers, test = _workers(seed=seed)
    cfg = FLConfig(rounds=rounds, lr=0.1, policy=policy,
                   case=Case.GD_CONVEX,
                   channel=ChannelConfig(sigma2=sigma2, p_max=10.0),
                   constants=LearningConstants(sigma2=sigma2),
                   backend=backend, seed=seed)
    return FLTrainer(linreg_model(), workers, cfg).run(
        key=jax.random.PRNGKey(seed), eval_data=test)


def test_linreg_converges_to_target():
    h = _run("inflota", rounds=250)
    p = h["params"]
    slope = float(p["w1"][0] * p["w2"][0])
    icept = float(p["b1"][0] * p["w2"][0])
    assert abs(slope + 2.0) < 0.35
    assert abs(icept - 1.0) < 0.25
    # MSE approaches the label-noise floor 0.4^2
    assert h["mse"][-1] < 0.25


def test_policy_ordering_perfect_inflota_random():
    mse = {p: float(np.mean(_run(p)["mse"][-10:]))
           for p in ("perfect", "inflota", "random")}
    assert mse["perfect"] <= mse["inflota"] * 1.10
    assert mse["inflota"] < mse["random"]


def test_noise_moves_steady_state_not_convergence():
    """Lemma 1 / Prop. 1: sigma^2 affects where we converge, not whether.

    sigma2 is kept within the contractive regime: at sigma2 >= ~0.5 the
    early-round clipping dynamics (Assumption-4 proxy near w_0, see
    benchmarks/theory_check.py) are chaotic enough that XLA:CPU's
    non-deterministic reduction order flips runs between converge/diverge.
    """
    lo = _run("inflota", sigma2=1e-4)
    hi = _run("inflota", sigma2=0.05)
    # both converge: late-window fluctuation small relative to the initial
    # transient (the high-noise run wobbles around its steady state)
    for h in (lo, hi):
        tail = np.asarray(h["mse"][-20:])
        head = np.asarray(h["mse"][:5])
        assert tail.std() < 0.3 * max(float(head.mean()), 1e-6) + 0.15
    assert float(np.mean(hi["mse"][-10:])) >= \
        float(np.mean(lo["mse"][-10:])) - 1e-3


def test_kernel_path_matches_jnp_path():
    """The kernel route uses a scalar eta (mean over entries) where the jnp
    route is entry-wise (footnote 4 allows either), so trajectories agree
    to ~1%, not bitwise; test_kernels.py checks bitwise vs the oracle."""
    a = _run("inflota", rounds=15)
    b = _run("inflota", rounds=15, backend="pallas")
    np.testing.assert_allclose(a["mse"], b["mse"], rtol=2e-2)


def test_use_kernels_deprecated_but_equivalent():
    """Legacy ``use_kernels=True`` warns and resolves to Backend.PALLAS."""
    from repro.fl.trainer import Backend
    cfg = FLConfig(use_kernels=True)
    with pytest.warns(DeprecationWarning, match="use_kernels"):
        assert cfg.resolved_backend() is Backend.PALLAS
    cfg = FLConfig(backend="pallas")
    assert cfg.resolved_backend() is Backend.PALLAS


def test_sgd_minibatch_runs_and_learns():
    workers, test = _workers(U=8, k_bar=30)
    cfg = FLConfig(rounds=150, lr=0.1, policy="inflota",
                   case=Case.SGD, k_b=8,
                   channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                   constants=LearningConstants(sigma2=1e-4), seed=0)
    h = FLTrainer(linreg_model(), workers, cfg).run(
        key=jax.random.PRNGKey(0), eval_data=test)
    assert h["mse"][-1] < h["mse"][0]
    assert h["mse"][-1] < 0.4


def test_mlp_nonconvex_learns():
    counts = partition.sample_counts(10, 40, seed=2)
    x, y = synthetic.mnist_like(int(np.sum(counts)) + 500, seed=2)
    workers = partition.partition(x[:-500], y[:-500], counts, seed=2)
    cfg = FLConfig(rounds=60, lr=0.1, policy="inflota",
                   case=Case.GD_NONCONVEX,
                   channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                   constants=LearningConstants(sigma2=1e-4), seed=2)
    h = FLTrainer(mlp_model(), workers, cfg).run(
        key=jax.random.PRNGKey(2), eval_data=(x[-500:], y[-500:]))
    assert h["accuracy"][-1] > 0.5          # 10 classes, chance = 0.1
    assert h["ce"][-1] < h["ce"][0]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_mlp_scan_matmuls_full_precision(backend):
    """Every matmul of a scanned MLP run (local updates, their gradients,
    eval) is pinned to full f32 precision, whatever the global default:
    a TPU would otherwise run them as one bfloat16 pass."""
    counts = partition.sample_counts(3, 8, seed=0)
    x, y = synthetic.mnist_like(int(np.sum(counts)) + 16, seed=0)
    workers = partition.partition(x[:-16], y[:-16], counts, seed=0)
    cfg = FLConfig(rounds=2, lr=0.1, policy="inflota",
                   case=Case.GD_NONCONVEX, backend=backend, scan=True)
    tr = FLTrainer(mlp_model(), workers, cfg)
    tr.run(key=jax.random.PRNGKey(0), eval_data=(x[-16:], y[-16:]))
    dots = [line for line in tr.compiled.as_text().splitlines()
            if " dot(" in line]
    assert len(dots) >= 6                # 2 forward + 2x2 backward, eval
    assert all("operand_precision={highest,highest}" in line
               for line in dots), dots
