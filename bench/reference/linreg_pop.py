"""Plain float32 reference of a population-scale Sec. VI-A round.

The paper's 1-neuron linear model y = w2 (w1 x + b1) (arXiv 2104.03490
Sec. VI-A) trained over the air by U workers, at the population sizes of
arXiv 2508.17697.  Each round: every worker takes one full-batch
gradient step on its own samples, the PS draws the channel, solves
Theorem 4 for (b, beta) per entry, the workers transmit with
Algorithm 1's clipping and the PS descales (eqs. 6-9).

The Theorem-4 search is written for U = 10^5 workers: with one gain per
worker the candidate matrix of eq. (43) factorizes, cand[i, d] = c_i s_d,
so candidate k's selected set {i : c_k <= c_i (1 + tol)} is the same for
every entry, and its denominator sum_i K_i beta_i is one lookup in the
sorted thresholds: O(U log U) instead of the dense O(U^2 D).  The
per-entry argmin over the U curves is then one (U, D) pass.  It imports
nothing of the system under test, and follows its seed conventions
(see ``mlp_fl``) so that the two trajectories can be compared.

``precision="bf16"`` runs the workers' local updates and the transmit
arithmetic in bfloat16: the control that ``correct`` must refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12
_TOL = 1e-6
# ravel order of the parameter dict (keys sorted): b1, w1, w2
NAMES = ("b1", "w1", "w2")


def init_params(key):
    """Flat (b1, w1, w2) of the Sec. VI-A model."""
    k1, k2 = jax.random.split(key)
    return jnp.concatenate([jnp.zeros((1,)),
                            0.1 * jax.random.normal(k1, (1,)),
                            1.0 + 0.1 * jax.random.normal(k2, (1,))])


def local_step(flat, x, y, mask, lr, dtype):
    """One full-batch GD step on the worker's real samples (eq. 4)."""
    def loss(f):
        b1, w1, w2 = f[0], f[1], f[2]
        err = w2 * (w1 * x[:, 0] + b1) - y[:, 0]
        return jnp.sum(err * err * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    f = flat.astype(dtype)
    g = jax.grad(loss)(f)
    return (f - jnp.asarray(lr, dtype) * g).astype(jnp.float32)


def search(h, k_i, w_abs, eta, p_max, numer, L, sigma2):
    """Theorem 4 for a rank-1 channel in O(U log U + U D).

    Returns (b (D,), cw (U,), s (D,)): beta[i, d] = b[d] <= cw[i] s[d] (1+tol).
    """
    cw = jnp.abs(jnp.sqrt(p_max) * h / jnp.maximum(k_i, _EPS))
    s = 1.0 / (w_abs + eta)
    thr = cw * (1.0 + _TOL)
    order = jnp.argsort(thr)
    thr_sorted = thr[order]
    csum = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(k_i[order])])
    # den_k = sum of K_i over workers whose threshold is >= c_k
    den = csum[-1] - csum[jnp.searchsorted(thr_sorted, cw, side="left")]
    bmat = cw[:, None] * s[None, :]
    r = (L * sigma2 / (2.0 * jnp.maximum(den[:, None] * bmat, _EPS) ** 2)
         + (numer / (2.0 * L * jnp.maximum(den, _EPS)))[:, None])
    kstar = jnp.argmin(r, axis=0)
    return cw[kstar] * s, cw, s


def make_round(X, Y, mask, k_i, *, lr, sigma2, p_max, L=1.0, mu=0.5,
               rho1=1.0, rho2=0.01, precision="highest"):
    """The round as a function (flat, prev, delta, t, key) -> (state, stats)."""
    U = k_i.shape[0]
    pmax = jnp.full((U,), float(p_max))
    K = jnp.sum(k_i)
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    def round_(flat, prev, delta, t, key):
        key_next, k_local, k_chan, _ = jax.random.split(key, 4)
        del k_local            # full-batch GD draws nothing
        W = jax.vmap(local_step, in_axes=(None, 0, 0, 0, None, None))(
            flat, X, Y, mask, lr, dtype)
        kg, kn = jax.random.split(jax.random.fold_in(k_chan, t), 2)
        h = jnp.maximum(jax.vmap(lambda i: jax.random.exponential(
            jax.random.fold_in(kg, i), ()))(jnp.arange(U)), 1e-3)
        noise = jnp.sqrt(sigma2) * jax.random.normal(kn, flat.shape)
        eta = jnp.abs(flat - prev) + 1e-8
        numer = K * rho1 + 2.0 * K * L * rho2 * delta          # eq. (35)
        b, cw, s = search(h, k_i, jnp.abs(flat), eta, pmax, numer, L,
                          sigma2)
        beta = (b[None, :] <= cw[:, None] * s[None, :] * (1.0 + _TOL)
                ).astype(jnp.float32)
        amp = jnp.abs((k_i[:, None] * b[None, :]).astype(dtype)
                      * W.astype(dtype) / h[:, None].astype(dtype))
        tx = (beta.astype(dtype) * jnp.sign(W).astype(dtype)
              * jnp.minimum(amp, jnp.sqrt(pmax)[:, None].astype(dtype)))
        y = jnp.sum((tx * h[:, None].astype(dtype)).astype(jnp.float32),
                    axis=0) + noise
        den_ki = jnp.sum(k_i[:, None] * beta, axis=0)
        den_keff = den_ki * b
        w_hat = jnp.where(den_keff > _EPS, y / jnp.maximum(den_keff, _EPS),
                          0.0)
        new = jnp.where(den_keff > _EPS, w_hat, flat)
        ratio = jnp.sum(K / jnp.maximum(den_ki, _EPS) - 1.0)
        inv2 = 1.0 / jnp.maximum(den_ki * b, _EPS) ** 2
        a_t = 1.0 - mu / L + rho2 * ratio
        b_t = rho1 / (2 * L) * ratio + jnp.sum(inv2) * L * sigma2 / 2
        snr = jnp.mean(new ** 2) / jnp.maximum(sigma2 * jnp.mean(inv2),
                                               _EPS)
        stats = {"selected": jnp.mean(jnp.sum(beta, axis=0)),
                 "b": jnp.mean(b), "a_t": a_t, "b_t": b_t,
                 "eta": jnp.mean(eta), "snr": snr}
        return (new, flat, b_t + a_t * delta, t + 1, key_next), stats

    return round_
