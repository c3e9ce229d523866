"""Plain float32 reference of the paper's Sec. VI-B round (arXiv 2104.03490).

One FL-over-the-air run of the 784-64-10 MLP: every worker takes one
minibatch SGD step (eq. 4), the PS draws the channel, decides (b, beta)
by its policy, the workers transmit with Algorithm 1's clipping, the MAC
superposes with noise and the PS descales (eqs. 6-9).  Written straight
from the paper in ``jax.numpy``: a dense U x D selection per entry, the
U-point line search of Theorem 4 as a loop over candidates, no kernels,
no batching tricks.  It imports nothing of the system under test.

It follows the system's seed conventions (which key feeds which draw),
because the benchmark compares trajectories, not distributions:

  key -> (k_init, k_round); per round k_round -> (next, local, chan, pol);
  worker i's minibatch key is split(fold_in(local, i), 1)[0] and sample
  j's priority uniform(fold_in(that, j)); the channel of round t uses
  split(fold_in(chan, t)) -> (gain, noise), worker i's gain
  exponential(fold_in(gain, i)) floored at 1e-3.

``precision="highest"`` runs the task matmuls at full f32 (what the
system states).  ``precision="bf16"`` rounds their operands to bfloat16
with f32 accumulation, the one-pass default of a TPU: the control that
``correct`` must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12
_TOL = 1e-6
D_IN, HIDDEN, CLASSES = 784, 64, 10
# ravel order of the parameter dict (keys sorted): b1, b2, w1, w2
LAYOUT = (("b1", (HIDDEN,)), ("b2", (CLASSES,)),
          ("w1", (D_IN, HIDDEN)), ("w2", (HIDDEN, CLASSES)))
D = sum(int(np.prod(s)) for _, s in LAYOUT)


def leaves(flat):
    """Split a flat parameter vector (leading axes kept) into its leaves."""
    out, ofs = {}, 0
    for name, shape in LAYOUT:
        n = int(np.prod(shape))
        out[name] = flat[..., ofs:ofs + n]
        ofs += n
    return out


def unflatten(flat):
    return {k: v.reshape(dict(LAYOUT)[k]) for k, v in leaves(flat).items()}


def flatten(p):
    return jnp.concatenate([p[k].reshape(-1) for k, _ in LAYOUT])


def init_params(key):
    k1, k2 = jax.random.split(key)
    return {"b1": jnp.zeros((HIDDEN,)), "b2": jnp.zeros((CLASSES,)),
            "w1": jax.random.normal(k1, (D_IN, HIDDEN)) * (2.0 / D_IN) ** 0.5,
            "w2": (jax.random.normal(k2, (HIDDEN, CLASSES))
                   * (2.0 / HIDDEN) ** 0.5)}


def _matmul(a, b, precision):
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def logits(p, x, precision):
    h = jax.nn.relu(_matmul(x, p["w1"], precision) + p["b1"])
    return _matmul(h, p["w2"], precision) + p["b2"]


def cross_entropy(p, x, y, precision):
    lg = logits(p, x, precision)
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1)
                    - jnp.take_along_axis(lg, y[:, None], axis=1)[:, 0])


def local_step(flat, x, y, mask, key, *, lr, k_b, precision):
    """One SGD step on k_b samples drawn without replacement (eq. 4)."""
    k = jax.random.split(key, 1)[0]
    pri = jax.vmap(lambda j: jax.random.uniform(jax.random.fold_in(k, j)))(
        jnp.arange(mask.shape[0]))
    idx = jnp.argsort(jnp.where(mask > 0, pri, jnp.inf))[:k_b]
    p = unflatten(flat)
    g = jax.grad(cross_entropy)(p, x[idx], y[idx], precision)
    return flatten(jax.tree.map(lambda w, gw: w - lr * gw, p, g))


def inflota_decision(h_est, k_eff, w_abs, eta, p_max, numer, L, sigma2):
    """Theorem 4: per entry, the candidate b of eq. (43) minimizing R_t.

    Candidates are tried in worker order and a later one wins only when
    strictly better (the first minimum).  Returns (b (D,), beta (U, D)).
    """
    cand = jnp.abs(jnp.sqrt(p_max)[:, None] * h_est[:, None]
                   / (k_eff[:, None] * (w_abs + eta)[None, :]))

    def body(k, best):
        best_r, best_b, best_beta = best
        b_k = cand[k]
        beta_k = (b_k[None, :] <= cand * (1.0 + _TOL)).astype(cand.dtype)
        den = jnp.sum(k_eff[:, None] * beta_k, axis=0)
        r_k = (L * sigma2 / (2.0 * jnp.maximum(den * b_k, _EPS) ** 2)
               + numer / (2.0 * L * jnp.maximum(den, _EPS)))
        take = r_k < best_r
        return (jnp.where(take, r_k, best_r), jnp.where(take, b_k, best_b),
                jnp.where(take[None, :], beta_k, best_beta))

    U, Dn = cand.shape
    init = (jnp.full((Dn,), jnp.inf), jnp.zeros((Dn,)), jnp.zeros((U, Dn)))
    _, b, beta = jax.lax.fori_loop(0, U, body, init)
    return b, beta


def transmit(W, h, h_est, beta, b, noise, k_eff, p_max):
    """Eqs. (6)-(9) with Algorithm 1's clipping: the PS estimate w_hat."""
    amp = jnp.abs(k_eff[:, None] * b[None, :] * W / h_est[:, None])
    tx = beta * jnp.sign(W) * jnp.minimum(amp, jnp.sqrt(p_max)[:, None])
    y = jnp.sum(tx * h[:, None], axis=0) + noise
    den = jnp.sum(k_eff[:, None] * beta, axis=0) * b
    return jnp.where(den > _EPS, y / jnp.maximum(den, _EPS), 0.0), den


def run(key, X, Y, mask, k_i, x_test, y_test, *, policy, rounds, lr, k_b,
        sigma2, p_max, L=1.0, mu=0.5, rho1=1.0, rho2=0.01,
        precision="highest"):
    """One whole run: the per-round history (round stats, then ce and
    accuracy on the test split after the round) and the final params."""
    U = k_i.shape[0]
    k_eff = jnp.full((U,), float(k_b))
    pmax = jnp.full((U,), float(p_max))
    K = jnp.sum(k_i)
    k_init, k_round = jax.random.split(key)
    flat0 = flatten(init_params(k_init))

    def aggregate(flat, prev, W, h, noise, k_pol):
        """-> (new params, delta increment (a_t, b_t), round stats)."""
        if policy == "perfect":
            new = jnp.sum(k_i[:, None] * W, axis=0) / K
            zero = jnp.float32(0.0)
            return new, (jnp.float32(1.0), zero), (
                jnp.float32(U), zero, jnp.float32(1.0 - mu / L), zero,
                zero, zero)
        eta = jnp.abs(flat - prev) + 1e-8
        if policy == "inflota":
            b, beta = inflota_decision(h, k_eff, jnp.abs(flat), eta, pmax,
                                       K * rho1, L, sigma2)
        elif policy == "random":
            kb, ksel = jax.random.split(k_pol)
            b = jnp.full(flat.shape, jax.random.exponential(kb, ()))
            sel = jax.vmap(lambda i: jax.random.bernoulli(
                jax.random.fold_in(ksel, i), 0.5, ()))(jnp.arange(U))
            beta = jnp.broadcast_to(sel.astype(jnp.float32)[:, None],
                                    (U, flat.shape[0]))
        else:
            raise ValueError(f"unknown policy {policy!r}")
        w_hat, den_keff = transmit(W, h, h, beta, b, noise, k_eff, pmax)
        new = jnp.where(den_keff > _EPS, w_hat, flat)
        den_ki = jnp.sum(k_i[:, None] * beta, axis=0)
        ratio = jnp.sum(K / jnp.maximum(den_ki, _EPS) - 1.0)
        inv2 = 1.0 / jnp.maximum(den_ki * b, _EPS) ** 2
        a_t = 1.0 - mu / L + rho2 * ratio
        b_t = rho1 / (2 * L) * ratio + jnp.sum(inv2) * L * sigma2 / 2
        snr = jnp.mean(new ** 2) / jnp.maximum(sigma2 * jnp.mean(inv2),
                                               _EPS)
        return new, (a_t, b_t), (jnp.mean(jnp.sum(beta, axis=0)),
                                 jnp.mean(b), a_t, b_t, jnp.mean(eta), snr)

    def round_(carry, _):
        flat, prev, delta, t, key = carry
        key_next, k_local, k_chan, k_pol = jax.random.split(key, 4)
        wkeys = jax.vmap(lambda i: jax.random.fold_in(k_local, i))(
            jnp.arange(U))
        W = jax.vmap(functools.partial(local_step, lr=lr, k_b=k_b,
                                       precision=precision),
                     in_axes=(None, 0, 0, 0, 0))(flat, X, Y, mask, wkeys)
        kg, kn = jax.random.split(jax.random.fold_in(k_chan, t), 2)
        h = jnp.maximum(jax.vmap(lambda i: jax.random.exponential(
            jax.random.fold_in(kg, i), ()))(jnp.arange(U)), 1e-3)
        noise = jnp.sqrt(sigma2) * jax.random.normal(kn, (flat.shape[0],))
        new, (a_t, b_t), stats = aggregate(flat, prev, W, h, noise, k_pol)
        ce, acc = metrics(new, x_test, y_test, precision)
        if policy == "perfect":
            a_t, b_t = jnp.float32(1.0), jnp.float32(0.0)  # delta unchanged
        return (new, flat, b_t + a_t * delta, t + 1, key_next), (
            *stats, ce, acc)

    carry = (flat0, flat0, jnp.float32(0.0), jnp.int32(0), k_round)
    (flat, *_), series = jax.lax.scan(round_, carry, None, length=rounds)
    return {"flat": flat, **dict(zip(SERIES, series))}


SERIES = ("selected", "b", "a_t", "b_t", "eta", "snr", "ce", "accuracy")


def metrics(flat, x_test, y_test, precision="highest"):
    """(ce, accuracy) of one parameter vector on the test split."""
    lg = logits(unflatten(flat), x_test, precision)
    ce = jnp.mean(jax.nn.logsumexp(lg, axis=-1)
                  - jnp.take_along_axis(lg, y_test[:, None], axis=1)[:, 0])
    return ce, jnp.mean((jnp.argmax(lg, -1) == y_test).astype(jnp.float32))


class Reference:
    """The reference for one configuration and data seed, run in blocks
    of experiments (one compiled program per policy and block size)."""

    def __init__(self, config: dict, data_seed: int,
                 precision: str = "highest"):
        from bench.reference import data
        (X, Y, mask, k_i), (xt, yt) = data.mlp_task(
            config["U"], config["k_bar"], data_seed,
            n_test=config["n_test"])
        self.arrays = tuple(jnp.asarray(a) for a in (X, Y, mask, k_i, xt, yt))
        self.config = config
        self.precision = precision
        self._fns = {}

    def run(self, policy: str, seeds, rounds: int) -> dict:
        """{series: (E, rounds), "flat": (E, D)} for these seeds."""
        key = (policy, rounds)
        if key not in self._fns:
            c = self.config
            self._fns[key] = jax.jit(jax.vmap(functools.partial(
                run, policy=policy, rounds=rounds, lr=c["lr"], k_b=c["k_b"],
                sigma2=c["sigma2"], p_max=c["p_max"],
                precision=self.precision), in_axes=(0,) + (None,) * 6))
        keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        out = self._fns[key](keys, *self.arrays)
        return {k: np.asarray(v) for k, v in out.items()}
