"""Pallas TPU kernel: fused single-pass OTA round (search + transmit).

Combines the Theorem-4 INFLOTA line search (eqs. 43-44, the per-entry
U-candidate argmin of R_t, eqs. 35-37) and the analog-aggregation
transmit/superposition/post-process (eqs. 6-9 + Algorithm 1 line 5) into
ONE VMEM pass over each block of entries.  The selection matrix ``beta``
— at (U, D) the largest intermediate of the round — lives only in
registers/VMEM and is never written to HBM.

VMEM/HBM traffic accounting (f32, per round of D entries, U workers,
dense-``h`` path; (U,)-shaped operands are negligible):

  composed ``inflota_search`` + ``ota_transmit_aggregate``:
      search   reads  h (U*D) + w_abs (D)            = (U+1) D
               writes b (D) + beta (U*D) + r (D)     = (U+2) D
      transmit reads  w (U*D) + h (U*D) + beta (U*D)
                      + b (D) + z (D)                = (3U+2) D
               writes w_hat (D)                      =        D
      total ≈ (5U + 6) D words of HBM traffic.

  fused ``ota_round``:
      reads  w (U*D) + h (U*D) + w_abs (D) + eta (D) + z (D) = (2U+3) D
      writes w_hat, b, den_keff, den_ki, sel                 =      5 D
      total ≈ (2U + 8) D — a ~2.5x reduction at U = 20, dominated by
      never materializing beta (U*D read + U*D write) and reading h once.

  rank-1 channel fast path (``h`` passed as (U, 1), matching the
  trainer's scalar-per-worker draw): both h reads drop from U*D to U,
      fused total ≈ (U + 8) D — roughly another third off at U = 20.

EVERY scalar the round consumes is a traced operand: ``eta`` (the
Assumption-4 slack, per entry) and ``numer`` (the case constant C, a
function of the traced Delta_{t-1}) are arrays, and the learning
constants ``L`` / ``sigma2`` ride with ``numer`` in a single (1, 3)
VMEM row.  So the whole round engine compiles once and runs under
``jax.jit`` / ``jax.lax.scan`` with no per-round recompilation or host
syncs, and the sweep engine can vmap a cohort that varies sigma2 / L /
numer per experiment over ONE kernel compilation.  The row is a full-array
block, so a vmapped cohort batches it to (E, 1, 3) with a (1, 3) block
per experiment — which the TPU lowering accepts, where a batched SMEM
vector is refused by its (8, 128) block rule.

Outputs are the per-entry reductions the trainer actually consumes —
w_hat, b, sum_i K_eff beta (descale denominator), sum_i K_i beta (the
A_t/B_t sampling statistic) and sum_i beta (selection count) — each (D,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-12
_TOL = 1e-6  # boundary tolerance: candidate k is feasible under b_k^max


def _kernel(w_ref, h_ref, hest_ref, wabs_ref, eta_ref, z_ref,
            keff_ref, ki_ref, pmax_ref, scal_ref,
            what_ref, b_ref, denk_ref, deni_ref, sel_ref,
            *, U: int):
    w = w_ref[...]            # (U, blk)
    h = h_ref[...]            # (U, blk) dense | (U, 1) rank-1 — TRUE gains
    h_est = hest_ref[...]     # same shapes — CSI estimate (== h if perfect)
    w_abs = wabs_ref[...]     # (1, blk)
    eta = eta_ref[...]        # (1, blk)
    z = z_ref[...]            # (1, blk)
    k_eff = keff_ref[...]     # (U, 1)
    k_i = ki_ref[...]         # (U, 1)
    p_max = pmax_ref[...]     # (U, 1)
    scal = scal_ref[...]      # (1, 3) traced [L, sigma2, numer]
    L = scal[:, 0:1]
    sigma2 = scal[:, 1:2]
    numer = scal[:, 2:3]

    sqrt_p = jnp.sqrt(p_max)

    # ---- Theorem-4 line search, eqs. (43)-(44): candidates + U-point argmin
    # The PS searches on what it can observe: the CSI estimate.  k_eff is
    # floored so MASKED workers (ragged cohorts hand in k_eff = p_max = 0)
    # produce candidate 0 — never selected — instead of a 0/0 NaN; real
    # workers (k_eff >= 1) are bit-identical to the unguarded form.
    cand = jnp.abs(sqrt_p * h_est
                   / (jnp.maximum(k_eff, _EPS) * (w_abs + eta)))  # (U, blk)
    best_r = jnp.full(w_abs.shape, jnp.inf, cand.dtype)          # (1, blk)
    best_b = jnp.zeros(w_abs.shape, cand.dtype)
    best_beta = jnp.zeros(cand.shape, cand.dtype)
    for k in range(U):  # static unroll: U is tens
        b_k = cand[k:k + 1, :]                                   # (1, blk)
        beta_k = (b_k <= cand * (1.0 + _TOL)).astype(cand.dtype)  # (U, blk)
        den = jnp.sum(k_eff * beta_k, axis=0, keepdims=True)     # (1, blk)
        r_k = (L * sigma2 / (2.0 * jnp.maximum(den * b_k, _EPS) ** 2)
               + numer / (2.0 * L * jnp.maximum(den, _EPS)))
        take = r_k < best_r                                      # (1, blk)
        best_r = jnp.where(take, r_k, best_r)
        best_b = jnp.where(take, b_k, best_b)
        best_beta = jnp.where(take, beta_k, best_beta)

    # ---- transmit + superposition + post-process, eqs. (6)-(9) + Alg.1 l.5
    # Workers invert their channel ESTIMATE; the MAC applies the true h.
    amp = jnp.abs(k_eff * best_b * w / h_est)
    tx = best_beta * jnp.sign(w) * jnp.minimum(amp, sqrt_p)
    y = jnp.sum(tx * h, axis=0, keepdims=True) + z               # (1, blk)
    den_keff = jnp.sum(k_eff * best_beta, axis=0, keepdims=True) * best_b
    what_ref[...] = jnp.where(den_keff > _EPS,
                              y / jnp.maximum(den_keff, _EPS), 0.0)
    b_ref[...] = best_b
    denk_ref[...] = den_keff
    deni_ref[...] = jnp.sum(k_i * best_beta, axis=0, keepdims=True)
    sel_ref[...] = jnp.sum(best_beta, axis=0, keepdims=True)


def _shard_tx_kernel(w_ref, h_ref, hest_ref, cw_ref, s_ref, b_ref,
                     keff_ref, ki_ref, pmax_ref, wm_ref,
                     y_ref, denk_ref, deni_ref, sel_ref):
    w = w_ref[...]            # (U_b, blk) this shard block's local updates
    h = h_ref[...]            # (U_b, 1)   true gains (rank-1)
    h_est = hest_ref[...]     # (U_b, 1)   CSI estimate
    cw = cw_ref[...]          # (U_b, 1)   Theorem-4 candidate coefficients
    s = s_ref[...]            # (1, blk)   1 / (|w_{t-1}| + eta)
    b = b_ref[...]            # (1, blk)   the DECIDED global power scaling
    k_eff = keff_ref[...]     # (U_b, 1)
    k_i = ki_ref[...]         # (U_b, 1)
    p_max = pmax_ref[...]     # (U_b, 1)
    wm = wm_ref[...]          # (U_b, 1)   real-worker mask (ones if none)

    # eq.-44 membership, rebuilt in VMEM from the rank-1 factorization —
    # op-for-op ``inflota.block_beta`` (same literal, same orientation),
    # so the tile agrees bit-for-bit with the jnp sharded path
    beta = (b <= cw * s * (1.0 + _TOL)).astype(w.dtype) * wm   # (U_b, blk)
    # Algorithm 1 line 5, op-for-op ``power.tx_signal`` (beta inside the
    # amp as there): workers invert the ESTIMATE, the MAC applies true h
    amp = jnp.abs(beta * k_eff * b / h_est * w)
    tx = beta * jnp.sign(w) * jnp.minimum(amp, jnp.sqrt(p_max))
    parts = (jnp.sum(tx * h, axis=0, keepdims=True),           # (1, blk)
             jnp.sum(k_eff * beta, axis=0, keepdims=True),
             jnp.sum(k_i * beta, axis=0, keepdims=True),
             jnp.sum(beta, axis=0, keepdims=True))
    outs = (y_ref, denk_ref, deni_ref, sel_ref)
    # the worker axis is the inner grid axis: the (1, blk) outputs stay
    # resident across it and accumulate the worker steps in order
    first = pl.program_id(1) == 0

    @pl.when(first)
    def _():
        for ref, p in zip(outs, parts):
            ref[...] = p

    @pl.when(jnp.logical_not(first))
    def _():
        for ref, p in zip(outs, parts):
            ref[...] += p


def scalar_row(L, sigma2, numer, dt):
    """The traced [L, sigma2, numer] as one (1, 3) kernel row."""
    return jnp.stack([jnp.asarray(v, dt).reshape(())
                      for v in (L, sigma2, numer)])[None, :]


# VMEM for the double-buffered inputs of one ``ota_shard_tx`` grid step:
# the (block_u, block_d) ``w`` tile and its seven (block_u, 1) worker
# columns, each lane-padded to 128.  Half of v5e's 16 MiB default scoped
# VMEM, leaving the rest to the kernel's (block_u, block_d) temporaries.
_SHARD_TX_VMEM = 8 << 20


def _shard_tx_tiles(U_b: int, D: int, block_d: int):
    """(block_u, block_d) of ``ota_shard_tx``: lanes no wider than D needs,
    and as few worker steps as fit ``_SHARD_TX_VMEM``.  One step
    (block_u = U_b) whenever the whole block fits."""
    block_d = min(block_d, -(-D // 128) * 128)
    max_rows = max(8, _SHARD_TX_VMEM // (4 * 2 * (block_d + 7 * 128))
                   // 8 * 8)
    steps = -(-U_b // max_rows)
    if steps == 1:
        return U_b, block_d
    rows = -(-U_b // steps)
    return -(-rows // 8) * 8, block_d


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ota_shard_tx(w, h, h_est, cw, s, b, k_eff, k_i, p_max, wmask=None,
                 *, block_d: int = 1024, interpret: bool):
    """One worker-shard block's transmit partials, fused in VMEM.

    The worker-sharded engine (``fl/worker_shard.py``) decides ``b``
    globally with the sharded Theorem-4 solver, then streams shard
    blocks through this kernel: the (U_b, D) beta tile is rebuilt from
    the rank-1 factorization ``(cw, s)`` inside VMEM (never written to
    HBM) and only the four (D,) partial reductions leave the kernel.

    Args:
      w:      (U_b, D) the block's local parameter vectors.
      h:      (U_b,) true channel gains (rank-1 — scalar per worker).
      h_est:  (U_b,) CSI estimate the transmit inversion uses.
      cw:     (U_b,) candidate coefficients |sqrt(P) h_est / k|.
      s:      (D,)   the 1 / (|w_{t-1}| + eta) statistic.
      b:      (D,)   decided per-entry power scaling (global optimum).
      k_eff:  (U_b,) descale weights; k_i: (U_b,) true sample counts;
      p_max:  (U_b,) power budgets; wmask: optional (U_b,) real-worker
              mask (None = all real; multiplying by 1.0 is exact).
      block_d: widest lane tile; narrowed to what D needs.
      interpret: run the Pallas interpreter (CPU) instead of compiling
              for the TPU; ``kernels.ops`` picks it from the backend.

    The grid walks (D tiles, worker tiles).  A block too large for VMEM
    is split into worker tiles padded with inert workers (zero mask and
    budget, unit gains), whose partials are exactly zero; the tiles'
    partials are summed in order.  A block that fits takes one worker
    step, which reduces in the same order as the jnp block ops.

    Returns (y_p, denk_p, deni_p, sel_p), each (D,): the block's
    superposition partial (no noise) and the three beta reductions
    (denk_p WITHOUT the * b — the combiner applies it after the
    cross-shard sum, mirroring ``selection.make_decision``).
    """
    U_b, D = w.shape
    dt = jnp.result_type(w.dtype, jnp.float32)
    if wmask is None:
        wmask = jnp.ones((U_b,), dt)
    block_u, block_d = _shard_tx_tiles(U_b, D, block_d)
    pad = (-D) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        s = jnp.pad(s, (0, pad), constant_values=1.0)
        b = jnp.pad(b, (0, pad))
    Dp = D + pad
    cols = [jnp.asarray(v, dt)
            for v in (h, h_est, cw, k_eff, k_i, p_max, wmask)]
    pad_u = (-U_b) % block_u
    if pad_u:
        w = jnp.pad(w, ((0, pad_u), (0, 0)))
        # h, h_est pad with 1 (the inversion divides by h_est); the
        # rest with 0, so the mask and budget zero every partial
        cols = [jnp.pad(v, (0, pad_u), constant_values=float(i < 2))
                for i, v in enumerate(cols)]
    row = pl.BlockSpec((1, block_d), lambda i, j: (0, i))
    col = pl.BlockSpec((block_u, 1), lambda i, j: (j, 0))
    y, denk, deni, sel = pl.pallas_call(
        _shard_tx_kernel,
        grid=(Dp // block_d, (U_b + pad_u) // block_u),
        in_specs=[
            pl.BlockSpec((block_u, block_d), lambda i, j: (j, i)),   # w
            col, col, col,                                    # h, h_est, cw
            row, row,                                         # s, b
            col, col, col, col,                    # k_eff, k_i, p_max, wm
        ],
        out_specs=[row, row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, Dp), dt)] * 4,
        interpret=interpret,
        name="ota_shard_tx",        # the kernel's name in a profiler trace
    )(w.astype(dt), *[v[:, None] for v in cols[:3]],
      jnp.asarray(s, dt)[None, :], jnp.asarray(b, dt)[None, :],
      *[v[:, None] for v in cols[3:]])
    return (y[0, :D], denk[0, :D], deni[0, :D], sel[0, :D])


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ota_round(w, h, w_abs, eta, noise, k_eff, k_i, p_max, numer,
              *, h_est=None, L, sigma2, block_d: int = 1024,
              interpret: bool):
    """Fused Theorem-4 search + OTA transmit/aggregate, one VMEM pass.

    Args:
      w:      (U, D) local parameter vectors.
      h:      (U, D) TRUE channel gains the MAC applies, or (U, 1) / (U,)
              for the rank-1 scalar-per-worker fast path (one coherent
              gain per worker).
      w_abs:  (D,) |w_{t-1}| at the PS.
      eta:    scalar or (D,) Assumption-4 slack (traced; per-entry OK).
      noise:  (D,) AWGN realization z_t.
      k_eff:  (U,) effective sample counts for the policy/descale
              (K_i for GD, K_b-filled for SGD).
      k_i:    (U,) true sample counts (the A_t/B_t statistic weights).
      p_max:  (U,) power budgets.
      numer:  scalar case constant C of eqs. 35-37 (traced: it depends on
              Delta_{t-1}).
      h_est:  optional CSI *estimate* (same shape conventions as ``h``):
              the Theorem-4 search and the workers' transmit inversion use
              the estimate while the superposition applies the true ``h``
              (imperfect-CSI scenarios, traced per round).  None =
              perfect CSI.
      L, sigma2: learning constants — TRACED scalars (floats work too):
              they enter the kernel through a (1, 3) VMEM row together
              with ``numer``, so sweeping them never recompiles.
      interpret: run the Pallas interpreter (CPU) instead of compiling
              for the TPU; ``kernels.ops`` picks it from the backend.

    Returns (w_hat, b, den_keff, den_ki, sel), each (D,):
      w_hat:    PS estimate (0 where no worker selected).
      b:        optimal per-entry power scaling.
      den_keff: sum_i K_eff beta_i * b   (descale denominator).
      den_ki:   sum_i K_i beta_i         (sampling-ratio statistic).
      sel:      sum_i beta_i             (selection count).
    """
    U, D = w.shape
    dt = jnp.result_type(w.dtype, jnp.float32)
    h = jnp.asarray(h, dt)
    if h.ndim == 1:
        h = h[:, None]
    h_est = h if h_est is None else jnp.asarray(h_est, dt)
    if h_est.ndim == 1:
        h_est = h_est[:, None]
    rank1 = h.shape[1] == 1
    rank1_est = h_est.shape[1] == 1
    eta = jnp.broadcast_to(jnp.asarray(eta, dt), (D,))
    pad = (-D) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        w_abs = jnp.pad(w_abs, (0, pad), constant_values=1.0)
        eta = jnp.pad(eta, (0, pad), constant_values=1.0)
        noise = jnp.pad(noise, (0, pad))
        if not rank1:
            h = jnp.pad(h, ((0, 0), (0, pad)), constant_values=1.0)
        if not rank1_est:
            h_est = jnp.pad(h_est, ((0, 0), (0, pad)), constant_values=1.0)
    Dp = D + pad
    grid = (Dp // block_d,)

    def _uspec(is_rank1):
        return (pl.BlockSpec((U, 1), lambda i: (0, 0)) if is_rank1
                else pl.BlockSpec((U, block_d), lambda i: (0, i)))

    row = pl.BlockSpec((1, block_d), lambda i: (0, i))
    col = pl.BlockSpec((U, 1), lambda i: (0, 0))
    scal = scalar_row(L, sigma2, numer, dt)

    kern = functools.partial(_kernel, U=U)
    what, b, denk, deni, sel = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((U, block_d), lambda i: (0, i)),   # w
            _uspec(rank1),                                  # h (true)
            _uspec(rank1_est),                              # h_est
            row,                                            # w_abs
            row,                                            # eta
            row,                                            # z
            col,                                            # k_eff
            col,                                            # k_i
            col,                                            # p_max
            pl.BlockSpec((1, 3), lambda i: (0, 0)),         # [L,sigma2,numer]
        ],
        out_specs=[row, row, row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, Dp), dt)] * 5,
        interpret=interpret,
        name="ota_round",           # the kernel's name in a profiler trace
    )(w.astype(dt), h, h_est, w_abs.astype(dt)[None, :], eta[None, :],
      noise.astype(dt)[None, :], jnp.asarray(k_eff, dt)[:, None],
      jnp.asarray(k_i, dt)[:, None], jnp.asarray(p_max, dt)[:, None],
      scal)
    return (what[0, :D], b[0, :D], denk[0, :D], deni[0, :D], sel[0, :D])
