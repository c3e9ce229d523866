"""Pallas TPU kernel: INFLOTA Theorem-4 line search, tiled over entries.

Algorithm 1 lines 8-11 loop over d = 1..D and, per entry, over U candidate
power-scaling factors — an O(D * U^2) scan that is the PS-side compute hot
spot of the paper (D = 50890 already in the paper's own MLP; D ~ 1e9+ when
the mechanism aggregates modern models at `entry` granularity).

TPU mapping: entries d tile the lanes (block_d, multiple of 128); workers sit
on sublanes.  The candidate loop (k = 1..U) is unrolled in-register: each
iteration builds the (U, block_d) feasibility mask beta_k via eq. (44),
reduces it over sublanes to the denominator, evaluates R_t (eqs. 35-37), and
keeps the running argmin.  One HBM read per operand, one write per output —
versus U materialized (U, D) candidate masks in the naive XLA lowering.

``eta`` / ``numer`` / ``L`` / ``sigma2`` are TRACED operands (eta as a
per-entry row, the other three as one (1, 3) VMEM row), matching
``kernels.ota_round``: a jitted caller — or a vmapped sweep cohort that
varies sigma2 / L / numer per experiment — never recompiles the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ota_round import scalar_row

_EPS = 1e-12
_TOL = 1e-6  # boundary tolerance: candidate k is feasible under b_k^max


def _kernel(h_ref, wabs_ref, eta_ref, ki_ref, pmax_ref, scal_ref,
            b_ref, beta_ref, r_ref, *, U: int):
    h = h_ref[...]                        # (U, blk) | (U, 1) rank-1
    w_abs = wabs_ref[...]                 # (1, blk)
    eta = eta_ref[...]                    # (1, blk)
    k_i = ki_ref[...]                     # (U, 1)
    p_max = pmax_ref[...]                 # (U, 1)
    scal = scal_ref[...]                  # (1, 3): [L, sigma2, numer]
    L = scal[:, 0:1]
    sigma2 = scal[:, 1:2]
    numer = scal[:, 2:3]

    # Candidate matrix, eq. (43)/(81): b_i^max per (worker, entry).  k_i
    # floored: masked workers (k_i = p_max = 0) give candidate 0, not NaN.
    cand = jnp.abs(jnp.sqrt(p_max) * h
                   / (jnp.maximum(k_i, _EPS) * (w_abs + eta)))   # (U, blk)

    best_r = jnp.full(w_abs.shape, jnp.inf, cand.dtype)          # (1, blk)
    best_b = jnp.zeros(w_abs.shape, cand.dtype)
    best_beta = jnp.zeros(h.shape, cand.dtype)

    for k in range(U):  # static unroll: U is tens
        b_k = cand[k:k + 1, :]                                   # (1, blk)
        beta_k = (b_k <= cand * (1.0 + _TOL)).astype(cand.dtype)  # (U, blk)
        den = jnp.sum(k_i * beta_k, axis=0, keepdims=True)       # (1, blk)
        r_k = (L * sigma2 / (2.0 * jnp.maximum(den * b_k, _EPS) ** 2)
               + numer / (2.0 * L * jnp.maximum(den, _EPS)))
        take = r_k < best_r                                      # (1, blk)
        best_r = jnp.where(take, r_k, best_r)
        best_b = jnp.where(take, b_k, best_b)
        best_beta = jnp.where(take, beta_k, best_beta)

    b_ref[...] = best_b
    beta_ref[...] = best_beta
    r_ref[...] = best_r


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def inflota_search(h, w_abs, k_i, p_max, *, eta, numer,
                   L, sigma2, block_d: int = 1024,
                   interpret: bool):
    """Per-entry optimal (b, beta, R) via the Theorem-4 U-point search.

    Args:
      h:      (U, D) channel gains, or (U, 1) / (U,) for the rank-1
              scalar-per-worker fast path (the gain is read once per
              worker instead of once per (worker, entry), cutting HBM
              reads by h's U*D words).
      w_abs:  (D,) |w_{t-1}|.
      k_i:    (U,) sample counts (pass K_b-filled for the SGD case).
      p_max:  (U,) power budgets.
      eta:    TRACED scalar or (D,) Assumption-4 slack.
      numer, L, sigma2: TRACED scalars (numer = case constant C of
        eqs. 35-37, computed by repro.core.objectives.case_numerator);
        they ride in a (1, 3) VMEM row, so none of them recompiles.
      interpret: run the Pallas interpreter (CPU) instead of compiling
        for the TPU; ``kernels.ops`` picks it from the backend.

    Returns: (b (D,), beta (U, D), r (D,)).
    """
    h = jnp.asarray(h)
    if h.ndim == 1:
        h = h[:, None]
    rank1 = h.shape[1] == 1
    U = h.shape[0]
    D = w_abs.shape[0]
    dt = jnp.result_type(h.dtype, jnp.float32)
    eta = jnp.broadcast_to(jnp.asarray(eta, dt), (D,))
    pad = (-D) % block_d
    if pad:
        if not rank1:
            h = jnp.pad(h, ((0, 0), (0, pad)), constant_values=1.0)
        w_abs = jnp.pad(w_abs, (0, pad), constant_values=1.0)
        eta = jnp.pad(eta, (0, pad), constant_values=1.0)
    Dp = D + pad
    grid = (Dp // block_d,)

    h_spec = (pl.BlockSpec((U, 1), lambda i: (0, 0)) if rank1
              else pl.BlockSpec((U, block_d), lambda i: (0, i)))
    scal = scalar_row(L, sigma2, numer, dt)
    kern = functools.partial(_kernel, U=U)
    b, beta, r = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            h_spec,                                         # h
            pl.BlockSpec((1, block_d), lambda i: (0, i)),   # w_abs
            pl.BlockSpec((1, block_d), lambda i: (0, i)),   # eta
            pl.BlockSpec((U, 1), lambda i: (0, 0)),         # k_i
            pl.BlockSpec((U, 1), lambda i: (0, 0)),         # p_max
            pl.BlockSpec((1, 3), lambda i: (0, 0)),         # [L,sigma2,numer]
        ],
        out_specs=[
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((U, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), dt),
            jax.ShapeDtypeStruct((U, Dp), dt),
            jax.ShapeDtypeStruct((1, Dp), dt),
        ],
        interpret=interpret,
    )(h.astype(dt), w_abs.astype(dt)[None, :], eta[None, :],
      jnp.asarray(k_i, dt)[:, None], jnp.asarray(p_max, dt)[:, None],
      scal)
    return b[0, :D], beta[:, :D], r[0, :D]
