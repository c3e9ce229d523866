"""Fused single-pass round engine: kernel equivalences + trainer modes.

Three families of checks (ISSUE 1 satellite):
  * rank-1 (U, 1) channel fast path == dense (U, D) path, for the fused
    ``ota_round`` kernel and both pre-existing kernels;
  * fused ``ota_round`` == the composed ``inflota_search`` +
    ``ota_transmit_aggregate`` kernels == the jnp core reference;
  * scan-based ``FLTrainer.run`` == Python-loop ``run`` on a fixed seed,
    for both backends.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg_core
from repro.core import inflota as inflota_core
from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data import partition, synthetic
from repro.fl.models import linreg_model
from repro.fl.trainer import FLConfig, FLTrainer
from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _round_inputs(rng, U, D):
    w = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
    h1 = jnp.asarray(rng.exponential(size=(U, 1)) + 1e-2, jnp.float32)
    w_abs = jnp.asarray(rng.uniform(0.01, 2.0, D), jnp.float32)
    eta = jnp.asarray(rng.uniform(0.01, 0.5, D), jnp.float32)
    z = jnp.asarray(rng.normal(size=D) * 1e-2, jnp.float32)
    k_eff = jnp.asarray(rng.integers(5, 20, U), jnp.float32)
    k_i = jnp.asarray(rng.integers(5, 20, U), jnp.float32)
    p_max = jnp.asarray(rng.uniform(0.5, 10.0, U), jnp.float32)
    return w, h1, w_abs, eta, z, k_eff, k_i, p_max


@pytest.mark.parametrize("U,D,block", [(3, 128, 128), (7, 700, 256),
                                       (20, 2048, 1024)])
def test_fused_round_rank1_equals_dense(U, D, block):
    rng = np.random.default_rng(U * 100 + D)
    w, h1, w_abs, eta, z, k_eff, k_i, p_max = _round_inputs(rng, U, D)
    hd = jnp.broadcast_to(h1, (U, D))
    kw = dict(L=2.0, sigma2=1e-3, block_d=block, interpret=True)
    out1 = ops.ota_round(w, h1, w_abs, eta, z, k_eff, k_i, p_max,
                         jnp.float32(7.5), **kw)
    outd = ops.ota_round(w, hd, w_abs, eta, z, k_eff, k_i, p_max,
                         jnp.float32(7.5), **kw)
    for a, b in zip(out1, outd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-6)


def test_fused_round_equals_composed_kernels():
    """ota_round == inflota_search + ota_transmit_aggregate (scalar eta)."""
    rng = np.random.default_rng(1)
    U, D = 9, 913
    w, h1, w_abs, _, z, k_eff, k_i, p_max = _round_inputs(rng, U, D)
    eta, numer, L, sigma2 = 0.3, 7.5, 2.0, 1e-3
    b0, beta0, _ = ops.inflota_search(
        h1, w_abs, k_eff, p_max, eta=eta, numer=numer, L=L, sigma2=sigma2,
        block_d=256, interpret=True)
    what0 = ops.ota_aggregate(w, h1, beta0, b0, z, k_eff, p_max,
                              block_d=256, interpret=True)
    what, b, den_keff, den_ki, sel = ops.ota_round(
        w, h1, w_abs, jnp.full((D,), eta), z, k_eff, k_i, p_max,
        jnp.float32(numer), L=L, sigma2=sigma2, block_d=256,
        interpret=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(what), np.asarray(what0),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(den_keff),
        np.asarray(jnp.sum(k_eff[:, None] * beta0, axis=0) * b0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(den_ki),
        np.asarray(jnp.sum(k_i[:, None] * beta0, axis=0)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sel),
                               np.asarray(jnp.sum(beta0, axis=0)),
                               rtol=1e-6)


def test_fused_round_matches_jnp_core():
    """ota_round == repro.core solve + aggregate (per-entry eta)."""
    rng = np.random.default_rng(2)
    U, D = 8, 517
    w, h1, w_abs, eta, z, k_eff, k_i, p_max = _round_inputs(rng, U, D)
    c = LearningConstants(L=2.0, mu=1.0, rho1=0.4, rho2=0.003, sigma2=1e-3)
    from repro.core.objectives import case_numerator
    numer = case_numerator(Case.GD_CONVEX, k_eff, c, 0.2)
    sol = inflota_core.solve(h1, k_eff, w_abs, eta, p_max, c,
                             Case.GD_CONVEX, delta_prev=0.2)
    want, _ = agg_core.ota_aggregate(w, h1, sol.beta, sol.b, k_eff, p_max, z)
    what, b, _, _, _ = ops.ota_round(
        w, h1, w_abs, eta, z, k_eff, k_i, p_max, numer,
        L=c.L, sigma2=c.sigma2, block_d=256, interpret=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(sol.b), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(what), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_round_ref_oracle():
    rng = np.random.default_rng(3)
    U, D = 6, 333
    args = _round_inputs(rng, U, D)
    kw = dict(L=1.5, sigma2=1e-4)
    out = ops.ota_round(*args, jnp.float32(3.0), block_d=128,
                        interpret=True, **kw)
    want = ref.ota_round_ref(*args, 3.0, **kw)
    for a, b in zip(out, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-6)


def test_fused_round_imperfect_csi_matches_oracle():
    """h_est != h: search + transmit inversion on the estimate, true h on
    the MAC — kernel vs composed jnp oracle, rank-1 and dense estimates."""
    rng = np.random.default_rng(7)
    U, D = 6, 450
    args = _round_inputs(rng, U, D)
    kw = dict(L=1.5, sigma2=1e-4)
    for h_est in (
            jnp.asarray(rng.exponential(size=(U, 1)) + 1e-2, jnp.float32),
            jnp.asarray(rng.exponential(size=(U, D)) + 1e-2, jnp.float32)):
        out = ops.ota_round(*args, jnp.float32(3.0), h_est=h_est,
                            block_d=128, interpret=True, **kw)
        want = ref.ota_round_ref(*args, 3.0, h_est=h_est, **kw)
        for a, b in zip(out, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=2e-6)
        # and the decisions really differ from the perfect-CSI ones
        perfect = ops.ota_round(*args, jnp.float32(3.0), block_d=128,
                                interpret=True, **kw)
        assert not np.allclose(np.asarray(out[1]), np.asarray(perfect[1]))


def test_fused_round_vmapped_batched_scalars_match_loop():
    """A sweep cohort's vmapped round: per-experiment L / sigma2 / numer
    (the (1, 3) scalar row, batched) equal one round per experiment."""
    rng = np.random.default_rng(5)
    E, U, D = 3, 6, 300
    ins = [jnp.stack(x) for x in zip(*[_round_inputs(rng, U, D)
                                       for _ in range(E)])]
    numer = jnp.asarray([7.5, 0.3, 42.0], jnp.float32)
    L = jnp.asarray([2.0, 1.0, 5.0], jnp.float32)
    sigma2 = jnp.asarray([1e-3, 1e-4, 1e-2], jnp.float32)

    def one(w, h1, w_abs, eta, z, k_eff, k_i, p_max, nu, l, s2):
        return ops.ota_round(w, h1, w_abs, eta, z, k_eff, k_i, p_max, nu,
                             L=l, sigma2=s2, block_d=128, interpret=True)

    got = jax.vmap(one)(*ins, numer, L, sigma2)
    for e in range(E):
        want = one(*[x[e] for x in ins], numer[e], L[e], sigma2[e])
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a[e]), np.asarray(b),
                                       rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("u_b", [12, 2500])
def test_shard_tx_matches_block_ops(u_b):
    """``ota_shard_tx`` == the jnp block ops of the worker-sharded engine
    (eq.-44 beta from the rank-1 factorization, Algorithm-1 clipping,
    the superposition added onto the carried y), also when the block is
    tiled over the worker axis (u_b = 2500 takes two padded worker
    steps), when D is not a multiple of the lane tile (a partial last
    tile), and when vmapped over experiments."""
    from repro.core import power

    rng = np.random.default_rng(u_b)
    D = 3

    def draw(d=D):
        h = jnp.asarray(rng.exponential(size=u_b) + 1e-2, jnp.float32)
        return dict(
            w=jnp.asarray(rng.normal(size=(u_b, d)), jnp.float32),
            h=h, h_est=h * jnp.asarray(rng.uniform(0.9, 1.1, u_b),
                                       jnp.float32),
            cw=jnp.asarray(rng.uniform(0.1, 2.0, u_b), jnp.float32),
            s=jnp.asarray(rng.uniform(0.5, 2.0, d), jnp.float32),
            b=jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32),
            k_eff=jnp.asarray(rng.integers(5, 20, u_b), jnp.float32),
            k_i=jnp.asarray(rng.integers(5, 20, u_b), jnp.float32),
            p_max=jnp.asarray(rng.uniform(0.5, 10.0, u_b), jnp.float32),
            wmask=jnp.asarray(rng.uniform(size=u_b) < 0.8, jnp.float32),
            y=jnp.asarray(rng.normal(size=d), jnp.float32))

    def kernel(x, block_d=16384):
        return ops.ota_shard_tx(x["w"], x["h"], x["h_est"], x["cw"],
                                x["s"], x["b"], x["k_eff"], x["k_i"],
                                x["p_max"], x["wmask"], y=x["y"],
                                block_d=block_d, interpret=True)

    def reference(x):
        beta = inflota_core.block_beta(x["b"], x["cw"], x["s"]) \
            * x["wmask"][:, None]
        y_terms = power.tx_signal(x["w"], beta, x["k_eff"], x["b"],
                                  x["h_est"][:, None], x["p_max"]) \
            * x["h"][:, None]
        return (x["y"] + jnp.sum(y_terms, axis=0),
                jnp.abs(x["y"]) + jnp.sum(jnp.abs(y_terms), axis=0))

    def close(got, x):
        # y reassociates over the worker tiles: its error is bounded by
        # the magnitude of its terms, not of the sum
        want, l1 = reference(x)
        assert np.all(np.abs(np.asarray(got) - np.asarray(want))
                      <= 1e-6 * np.asarray(l1))

    xs = [draw(), draw()]
    batched = jax.vmap(kernel)(jax.tree.map(lambda *v: jnp.stack(v), *xs))
    for e, x in enumerate(xs):
        for got in (kernel(x), batched[e]):
            close(got, x)
    wide = draw(300)
    close(kernel(wide, block_d=128), wide)


def test_search_kernel_rank1_equals_dense():
    rng = np.random.default_rng(4)
    U, D = 11, 640
    h1 = jnp.asarray(rng.exponential(size=(U, 1)) + 1e-2, jnp.float32)
    w_abs = jnp.asarray(rng.uniform(0.01, 2.0, D), jnp.float32)
    k_i = jnp.asarray(rng.integers(5, 30, U), jnp.float32)
    p_max = jnp.asarray(rng.uniform(0.5, 10.0, U), jnp.float32)
    kw = dict(eta=0.3, numer=7.5, L=2.0, sigma2=1e-3, block_d=256,
              interpret=True)
    b0, beta0, r0 = ops.inflota_search(jnp.broadcast_to(h1, (U, D)),
                                       w_abs, k_i, p_max, **kw)
    b1, beta1, r1 = ops.inflota_search(h1, w_abs, k_i, p_max, **kw)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(beta1), np.asarray(beta0))


def test_transmit_kernel_rank1_equals_dense():
    rng = np.random.default_rng(5)
    U, D = 10, 500
    w = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
    h1 = jnp.asarray(rng.exponential(size=(U, 1)) + 1e-2, jnp.float32)
    beta1 = jnp.asarray(rng.integers(0, 2, (U, 1)), jnp.float32)
    b = jnp.asarray(rng.uniform(0.5, 2.0, D), jnp.float32)
    z = jnp.asarray(rng.normal(size=D) * 1e-2, jnp.float32)
    k_i = jnp.asarray(rng.integers(5, 20, U), jnp.float32)
    p_max = jnp.asarray(rng.uniform(0.5, 10.0, U), jnp.float32)
    out1 = ops.ota_aggregate(w, h1, beta1, b, z, k_i, p_max,
                             block_d=128, interpret=True)
    outd = ops.ota_aggregate(w, jnp.broadcast_to(h1, (U, D)),
                             jnp.broadcast_to(beta1, (U, D)), b, z, k_i,
                             p_max, block_d=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(outd),
                               rtol=2e-6, atol=2e-6)


# ------------------------------------------------------------- trainer modes

def _workers(U=8, k_bar=20, seed=0):
    counts = partition.sample_counts(U, k_bar, seed=seed)
    x, y = synthetic.linreg(int(np.sum(counts)) + 128, seed=seed)
    return (partition.partition(x, y, counts, seed=seed),
            (x[-128:], y[-128:]))


def _run(policy="inflota", backend="jnp", scan=False, rounds=10):
    workers, test = _workers()
    cfg = FLConfig(rounds=rounds, lr=0.1, policy=policy,
                   case=Case.GD_CONVEX,
                   channel=ChannelConfig(sigma2=1e-4, p_max=10.0),
                   constants=LearningConstants(sigma2=1e-4),
                   backend=backend, scan=scan, seed=0)
    return FLTrainer(linreg_model(), workers, cfg).run(
        key=jax.random.PRNGKey(0), eval_data=test)


@pytest.mark.parametrize("policy", ["inflota", "random", "perfect"])
def test_scan_run_equals_loop_run(policy):
    a = _run(policy=policy, scan=False)
    b = _run(policy=policy, scan=True)
    for key in ("mse", "selected", "b"):
        np.testing.assert_allclose(a[key], b[key], rtol=1e-6, atol=1e-7)
    for leaf_a, leaf_b in zip(jax.tree.leaves(a["params"]),
                              jax.tree.leaves(b["params"])):
        np.testing.assert_allclose(np.asarray(leaf_a), np.asarray(leaf_b),
                                   rtol=1e-6, atol=1e-7)


def test_scan_run_pallas_backend():
    a = _run(backend="jnp", scan=True, rounds=6)
    b = _run(backend="pallas", scan=True, rounds=6)
    np.testing.assert_allclose(a["mse"], b["mse"], rtol=1e-3)
    np.testing.assert_allclose(a["selected"], b["selected"], atol=1e-6)
