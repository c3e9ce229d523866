"""Pallas TPU kernel: fused OTA transmit + superposition + PS post-process.

This is the per-entry hot loop of analog aggregation (paper eqs. 6-9 +
Algorithm 1 line 5) fused into one VMEM pass:

  per entry d (lane) and worker i (sublane):
      amp   = | K_i * b[d] / h[i,d] * w[i,d] |
      tx    = beta[i,d] * sign(w) * min(amp, sqrt(Pmax_i))      (clip, Alg.1)
      y[d]  = sum_i tx * h[i,d]  + z[d]                          (eq. 8)
      den   = sum_i K_i * beta[i,d] * b[d]
      w_hat = y / den   (0 where den == 0)                       (eq. 9)

TPU mapping: D is tiled along lanes in blocks of `block_d` (multiple of 128);
the worker axis U lives on sublanes and is reduced in-register — U is tens,
so a (U, block_d) tile comfortably fits VMEM (U=32, block=2048, f32 ->
256 KiB/operand).  Everything is VPU elementwise + a sublane reduction; the
fusion saves 4 HBM round-trips versus the naive composition (tx, y, den,
w_hat materialized separately).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-12


def _kernel(w_ref, h_ref, hest_ref, beta_ref, b_ref, z_ref, ki_ref,
            pmax_ref, out_ref):
    w = w_ref[...]          # (U, blk)
    h = h_ref[...]          # (U, blk) | (U, 1) rank-1 — TRUE gains
    h_est = hest_ref[...]   # same shapes — CSI estimate (== h if perfect)
    beta = beta_ref[...]    # (U, blk) | (U, 1) rank-1
    b = b_ref[...]          # (1, blk)
    z = z_ref[...]          # (1, blk)
    k_i = ki_ref[...]       # (U, 1)
    p_max = pmax_ref[...]   # (U, 1)

    # Workers invert their channel ESTIMATE; the MAC applies the true h.
    amp = jnp.abs(k_i * b * w / h_est)
    tx = beta * jnp.sign(w) * jnp.minimum(amp, jnp.sqrt(p_max))
    y = jnp.sum(tx * h, axis=0, keepdims=True) + z            # (1, blk)
    den = jnp.sum(k_i * beta, axis=0, keepdims=True) * b      # (1, blk)
    w_hat = jnp.where(den > _EPS, y / jnp.maximum(den, _EPS), 0.0)
    out_ref[...] = w_hat


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ota_transmit_aggregate(w, h, beta, b, noise, k_i, p_max,
                           *, h_est=None, block_d: int = 1024,
                           interpret: bool):
    """Fused OTA aggregation round.

    Args:
      w:          (U, D) float array.
      h, beta:    (U, D) float arrays, or (U, 1) / (U,) for the rank-1
                  fast path (scalar-per-worker gain / selection — each
                  read once per worker instead of once per entry).
                  ``h`` is the TRUE gain the MAC applies.  Masked
                  (ragged-cohort-padded) workers arrive with k_i = 0 and
                  a zeroed beta row: their amp and denominator
                  contributions vanish without any special casing here.
      b, noise:   (D,) float arrays.
      k_i, p_max: (U,) float arrays.
      h_est:      optional CSI estimate (same shape conventions as ``h``)
                  used by the workers' transmit-side channel inversion;
                  None = perfect CSI (h_est = h).
      block_d:    lane tile (multiple of 128 on real TPU).
      interpret:  run the Pallas interpreter (CPU validation mode).

    Returns: (D,) post-processed global parameter estimate w_hat.
    """
    U, D = w.shape
    dt = jnp.result_type(w.dtype, jnp.asarray(h).dtype, jnp.float32)
    h = jnp.asarray(h)
    beta = jnp.asarray(beta)
    if h.ndim == 1:
        h = h[:, None]
    h_est = h if h_est is None else jnp.asarray(h_est)
    if h_est.ndim == 1:
        h_est = h_est[:, None]
    if beta.ndim == 1:
        beta = beta[:, None]
    h_rank1 = h.shape[1] == 1
    hest_rank1 = h_est.shape[1] == 1
    beta_rank1 = beta.shape[1] == 1
    pad = (-D) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        if not h_rank1:
            h = jnp.pad(h, ((0, 0), (0, pad)), constant_values=1.0)
        if not hest_rank1:
            h_est = jnp.pad(h_est, ((0, 0), (0, pad)), constant_values=1.0)
        if not beta_rank1:
            beta = jnp.pad(beta, ((0, 0), (0, pad)))
        b = jnp.pad(b, (0, pad), constant_values=1.0)
        noise = jnp.pad(noise, (0, pad))
    Dp = D + pad
    grid = (Dp // block_d,)

    def _uspec(rank1):
        return (pl.BlockSpec((U, 1), lambda i: (0, 0)) if rank1
                else pl.BlockSpec((U, block_d), lambda i: (0, i)))

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((U, block_d), lambda i: (0, i)),   # w
            _uspec(h_rank1),                                # h (true)
            _uspec(hest_rank1),                             # h_est
            _uspec(beta_rank1),                             # beta
            pl.BlockSpec((1, block_d), lambda i: (0, i)),   # b
            pl.BlockSpec((1, block_d), lambda i: (0, i)),   # z
            pl.BlockSpec((U, 1), lambda i: (0, 0)),         # k_i
            pl.BlockSpec((U, 1), lambda i: (0, 0)),         # p_max
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), dt),
        interpret=interpret,
    )(w.astype(dt), h.astype(dt), h_est.astype(dt), beta.astype(dt),
      b.astype(dt)[None, :], noise.astype(dt)[None, :],
      jnp.asarray(k_i, dt)[:, None], jnp.asarray(p_max, dt)[:, None])
    return out[0, :D]
