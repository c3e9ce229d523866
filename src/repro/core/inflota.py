"""INFLOTA joint worker-selection / power-scaling optimizer.

Implements Theorem 4 + problem P4: for each parameter entry d, the optimal
power scaling factor b_t lies in the U-point set

    b^(k) = | sqrt(P_k^max) h_k / (K_k (|w_{t-1}| + eta)) |,  k = 1..U   (43)

with the selection vector determined from b by feasibility (eq. 44):

    beta_i(b) = H( P_i^max - | K_i b (|w_{t-1}| + eta) / h_i | )

so P3 reduces to a discrete line search over U candidates per entry.

Everything is vectorized over D entries: the search is an O(D U^2) batch of
elementwise ops + reductions, jit-friendly, and the exact computation the
Pallas kernel `repro.kernels.inflota_search` tiles over VMEM.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import power as power_lib
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case, case_numerator, r_t

_EPS = 1e-12
_TOL = 1e-6   # eq.-44 boundary tolerance — matches _solve_rank1's literal


class InflotaSolution(NamedTuple):
    b: jax.Array          # (D,) optimal power scaling per entry
    beta: jax.Array       # (U, D) optimal selection per entry, {0,1}
    r: jax.Array          # (D,) attained objective value


def candidate_b(h, k_i, w_prev_abs, eta, p_max) -> jax.Array:
    """Eq. (43): the (U, D) matrix of candidate scaling factors."""
    return power_lib.b_max_per_worker(h, k_i, w_prev_abs, eta, p_max)


def beta_of_b(b, h, k_i, w_prev_abs, eta, p_max) -> jax.Array:
    """Eq. (44): selection implied by a given b.  b: (D,) -> beta: (U, D).

    beta_i = 1  iff  P_i^max - | K_i b (|w|+eta) / h_i |  > 0.  Following the
    derivation (81) this is equivalent to b <= b_i^max; we use the closed
    feasibility test with a tolerant >= so the candidate worker k itself is
    always selected under b = b_k^max (the paper's strict Heaviside excludes
    the boundary only through floating-point accident).
    """
    bmax = candidate_b(h, k_i, w_prev_abs, eta, p_max)    # (U, D)
    return (b[None, :] <= bmax * (1.0 + 1e-6)).astype(jnp.float32)


def solve(h, k_i, w_prev_abs, eta, p_max, c: LearningConstants,
          case: Case = Case.GD_CONVEX, delta_prev: float = 0.0,
          K_b: float | None = None) -> InflotaSolution:
    """P4 line search, vectorized over entries.

    Args:
      h:           (U, D) channel gains this round, or (U, 1) / (U,) for
                   the rank-1 scalar-per-worker draw (broadcast against
                   the D entries of ``w_prev_abs`` without materializing
                   the dense matrix at this call site).
      k_i:         (U,) local dataset sizes.
      w_prev_abs:  (D,) |w_{t-1}| at the PS.
      eta:         scalar (or (D,)) bounded-update constant (Assumption 4).
      p_max:       (U,) or scalar power budgets.
      c:           learning constants (L, mu, rho1, rho2, sigma2).
      case:        which R_t to minimize (eqs. 35-37).
      delta_prev:  Delta_{t-1}, treated as a constant during round t.
      K_b:         mini-batch size for the SGD case.

    Returns InflotaSolution with per-entry optimal (b, beta, R).
    """
    h = jnp.asarray(h)
    if h.ndim == 1:
        h = h[:, None]
    U = h.shape[0]
    w_prev_abs = jnp.asarray(w_prev_abs)
    D = w_prev_abs.shape[0]
    dt = jnp.result_type(h.dtype, w_prev_abs.dtype, float)
    numer = case_numerator(case, k_i, c, delta_prev, K_b)
    if h.shape[1] == 1:
        return _solve_rank1(h[:, 0], k_i, w_prev_abs, eta, p_max, c,
                            numer, dt, K_b)
    cand = candidate_b(h, k_i, w_prev_abs, eta, p_max).astype(dt)  # (U, D)

    def eval_candidate(k, best):
        best_r, best_b, best_beta = best
        b_k = cand[k]                                     # (D,)
        beta_k = beta_of_b(b_k, h, k_i, w_prev_abs, eta, p_max).astype(dt)
        r_k = r_t(beta_k, b_k, k_i, c, numer, K_b=K_b).astype(dt)  # (D,)
        take = r_k < best_r
        return (jnp.where(take, r_k, best_r),
                jnp.where(take, b_k, best_b),
                jnp.where(take[None, :], beta_k, best_beta))

    init = (jnp.full((D,), jnp.inf, dt),
            jnp.zeros((D,), dt),
            jnp.zeros((U, D), dt))
    best_r, best_b, best_beta = jax.lax.fori_loop(
        0, U, eval_candidate, init)
    return InflotaSolution(b=best_b, beta=best_beta, r=best_r)


def _solve_rank1(h_w, k_i, w_prev_abs, eta, p_max, c: LearningConstants,
                 numer, dt, K_b: float | None = None) -> InflotaSolution:
    """Rank-1 channel fast path: O(U^2 + U D) instead of O(U^2 D).

    With one coherent gain per worker, the candidate matrix (43)
    factorizes as ``cand[i, d] = c_i * s_d`` with ``c_i = sqrt(P_i) h_i /
    K_i`` and ``s_d = 1 / (|w_d| + eta_d) > 0``.  The feasibility test
    (44) then loses its entry dependence —

        beta_k[i, d] = (c_k s_d <= c_i s_d (1+tol)) = (c_k <= c_i (1+tol))

    — so each candidate's selected set, and with it the denominator
    ``den_k = sum_i K_i beta_k[i]``, is a PER-WORKER SCALAR.  R_t[d]
    becomes a family of U curves ``A_k / s_d^2 + B_k`` over the single
    statistic s_d, and the per-entry search is one argmin over their
    lower envelope: U^2 scalar work + one O(U D) evaluation, versus the
    generic path's U full (U, D) mask builds.  This is the jnp twin of
    the Pallas kernels' rank-1 fast path (which additionally saves the
    h reads); the generic entry-wise search remains for dense h.
    """
    U = h_w.shape[0]
    k_arr = jnp.asarray(k_i, dt)
    # R_t's denominator uses K_b in the SGD case (paper note under (38b)),
    # exactly as r_t() does on the generic path; candidates (43) keep k_i
    k_eff = jnp.full_like(k_arr, K_b) if K_b is not None else k_arr
    p_arr = jnp.broadcast_to(jnp.asarray(p_max, dt), (U,))
    # K_i floored so masked workers (k_i = p_max = 0) give cw = 0, not NaN
    cw = jnp.abs(jnp.sqrt(p_arr) * h_w.astype(dt)
                 / jnp.maximum(k_arr, _EPS))                      # (U,)
    s = (1.0 / (w_prev_abs + eta)).astype(dt)                     # (D,)
    # feas[i, k] = worker i accepts candidate k's scaling (eq. 44)
    feas = cw[None, :] <= cw[:, None] * (1.0 + 1e-6)              # (U, U)
    den = jnp.sum(k_eff[:, None] * feas, axis=0)                  # (U,)
    bmat = cw[:, None] * s[None, :]                               # (U, D)
    r_all = (c.L * c.sigma2
             / (2.0 * jnp.maximum(den[:, None] * bmat, _EPS) ** 2)
             + (numer / (2.0 * c.L * jnp.maximum(den, _EPS)))[:, None])
    kstar = jnp.argmin(r_all, axis=0)            # first-min tie-break, as
    b = jnp.take(cw, kstar) * s                  # the sequential search
    r = jnp.take_along_axis(r_all, kstar[None, :], axis=0)[0]
    beta = (b[None, :] <= bmat * (1.0 + 1e-6)).astype(dt)
    return InflotaSolution(b=b, beta=beta, r=r)


# ------------------------------------------------------- sharded search
#
# Worker-sharded twin of ``_solve_rank1`` for the million-worker tier
# (``fl/worker_shard.py``): the worker axis is split into ``n_shards``
# contiguous blocks of ``U_b = U / n_shards`` workers, and no step ever
# touches more than one ``(U_b, D)`` tile.  The global O(U log U) sort of
# the dense path becomes per-shard sorted-prefix summaries (O(U_b log
# U_b) each) that cross shards as (U,)-sized side information — never as
# (U, D) blocks — and the per-entry argmin reduces lexicographically
# (min r, then min global worker index), reproducing ``jnp.argmin``'s
# first-min tie-break exactly.
#
# Exactness contract (pinned by ``tests/test_worker_sharded*.py``): for
# every shard count, ``solve_sharded`` returns bit-identical (b, beta, r)
# to ``solve`` on a rank-1 channel.  The three ingredients:
#
#   * the candidate coefficients ``cw`` and the per-entry curve values
#     r_all[k, d] repeat ``_solve_rank1``'s scalar op order exactly
#     (same expressions, same _EPS floors, same (1 + 1e-6) tolerance
#     ORIENTATION — the predicate is never rewritten algebraically);
#   * the denominators ``den_k = sum_i k_eff_i [cw_k <= cw_i (1+tol)]``
#     are sums of integer-valued f32 (sample counts), so any summation
#     order yields the same float as the dense masked sum while the
#     total stays below 2^24 (f32's exact-integer range; ~16.7M total
#     samples — beyond that the sharded value is still deterministic
#     for a given shard count, just not bit-comparable to the dense
#     path's own rounding);
#   * two-level argmin: the within-shard argmin picks the lowest local
#     index, the cross-shard argmin over the stacked minima picks the
#     lowest shard — together the lowest global index, since equal
#     minima are equal bit patterns.

class ShardedRank1(NamedTuple):
    """Rank-1 sharded solution WITHOUT the (U, D) beta.

    ``beta`` is reconstructed per shard on demand (``block_beta``) so the
    caller streams (U_b, D) tiles instead of materializing (U, D).
    """

    b: jax.Array       # (D,)   optimal power scaling per entry
    r: jax.Array       # (D,)   attained objective value
    kstar: jax.Array   # (D,)   global index of the winning candidate, i32
    cw: jax.Array      # (S, U_b) candidate coefficients, shard-blocked
    s: jax.Array       # (D,)   the 1 / (|w| + eta) statistic


def rank1_candidates(h_w, k_arr, p_max, w_prev_abs, eta, dt):
    """The two rank-1 factors of the candidate matrix (43): ``cand[i, d]
    = cw[i] * s[d]`` — op-for-op the expressions of ``_solve_rank1``."""
    U = h_w.shape[0]
    k_arr = jnp.asarray(k_arr, dt)
    p_arr = jnp.broadcast_to(jnp.asarray(p_max, dt), (U,))
    cw = jnp.abs(jnp.sqrt(p_arr) * h_w.astype(dt)
                 / jnp.maximum(k_arr, _EPS))                      # (U,)
    s = (1.0 / (w_prev_abs + eta)).astype(dt)                     # (D,)
    return cw, s


def block_summary(cw_blk, keff_blk):
    """One shard's sorted-prefix summary for the exact den reduction.

    Worker i accepts candidate k iff ``cw_k <= cw_i * (1 + tol)`` (the
    feasibility predicate of ``_solve_rank1``, same orientation).  Sorting
    the per-worker thresholds ``thr_i = cw_i * (1 + tol)`` ascending with
    a prefix sum of the matching k_eff turns "sum k_eff over accepting
    workers" into one searchsorted lookup per candidate — O(log U_b)
    instead of O(U_b), and only (U_b,)-sized arrays ever cross shards.

    Returns (thr_sorted (U_b,), csum0 (U_b + 1,)): ``csum0[j]`` is the
    k_eff mass of the j smallest thresholds (csum0[0] = 0), so a shard's
    den contribution for candidate value v is ``csum0[-1] -
    csum0[searchsorted(thr_sorted, v, 'left')]`` — exactly the strict
    complement of the ``thr_i < v`` count, i.e. the ``cw_k <= thr_i``
    mass.  Tie order inside the sort is irrelevant: equal thresholds sit
    in one run and 'left' indexes its boundary.
    """
    thr = cw_blk * (1.0 + _TOL)
    order = jnp.argsort(thr)
    thr_sorted = jnp.take(thr, order)
    csum = jnp.cumsum(jnp.take(keff_blk, order))
    csum0 = jnp.concatenate([jnp.zeros((1,), csum.dtype), csum])
    return thr_sorted, csum0


def block_den(cw_blk, thr_sorted, csum0):
    """Exact denominators for one shard's candidates against ALL shards.

    Args:
      cw_blk:     (U_b,) this shard's candidate coefficients.
      thr_sorted: (S, U_b) every shard's sorted thresholds.
      csum0:      (S, U_b + 1) every shard's k_eff prefix sums.

    Scans the S summaries in shard order, so the accumulation order is a
    pure function of the logical shard count — independent of how many
    devices execute it (the mesh and single-device paths agree bitwise).
    """
    def add(acc, xs):
        ts, cs = xs
        j = jnp.searchsorted(ts, cw_blk, side="left")
        return acc + (cs[-1] - cs[j]), None

    den, _ = jax.lax.scan(add, jnp.zeros_like(cw_blk),
                          (thr_sorted, csum0))
    return den


def block_envelope(cw_blk, den_blk, s, c: LearningConstants, numer):
    """One shard's slice of the per-entry lower envelope of R_t curves.

    Evaluates this shard's U_b candidate curves over all D entries —
    the (U_b, D) tile is the largest intermediate — with the exact
    expressions of ``_solve_rank1``, and reduces to the shard-local
    argmin.  Returns (rmin (D,), kloc (D,), cw_star (D,)).
    """
    bmat = cw_blk[:, None] * s[None, :]                       # (U_b, D)
    r_blk = (c.L * c.sigma2
             / (2.0 * jnp.maximum(den_blk[:, None] * bmat, _EPS) ** 2)
             + (numer / (2.0 * c.L
                         * jnp.maximum(den_blk, _EPS)))[:, None])
    kloc = jnp.argmin(r_blk, axis=0)
    # the min is the value at the argmin, bit for bit, with no (D,)-long
    # gather index array
    rmin = jnp.min(r_blk, axis=0)
    cw_star = jnp.take(cw_blk, kloc)
    return rmin, kloc, cw_star


def reduce_envelopes(rmin, kloc, cw_star, s, u_b: int):
    """Cross-shard argmin of the stacked per-shard envelopes (the
    comparison form; the engine folds shards with ``envelope_step``).

    ``jnp.argmin`` over the shard axis keeps the FIRST shard attaining
    the minimum, and each shard's ``kloc`` is its first local minimizer,
    so the composite is the global first-min tie-break of the dense
    search.  Returns (b (D,), r (D,), kstar (D,) global i32).
    """
    sidx = jnp.argmin(rmin, axis=0)                           # (D,)
    r = jnp.take_along_axis(rmin, sidx[None, :], axis=0)[0]
    kstar = (jnp.take_along_axis(kloc, sidx[None, :], axis=0)[0]
             + sidx.astype(kloc.dtype) * u_b)
    b = jnp.take_along_axis(cw_star, sidx[None, :], axis=0)[0] * s
    return b, r, kstar.astype(jnp.int32)


def envelope_step(carry, cw_blk, den_blk, s, c: LearningConstants, numer,
                  offset):
    """Fold one shard's envelope into the running per-entry first-min.

    ``carry`` is (rmin (D,), kstar (D,) i32) after the earlier shards,
    starting from (+inf, 0); ``offset`` is this shard's first global
    worker index.  A later shard wins an entry only when strictly
    better, so ties keep the earlier shard, and each shard's own argmin
    keeps its first local minimizer: the result is bit for bit what
    ``reduce_envelopes`` takes from the stacked envelopes, with no
    (S, D) stack.  The winner's b is ``cw[kstar] * s``.
    """
    rmin, kstar = carry
    r_blk, kloc, _ = block_envelope(cw_blk, den_blk, s, c, numer)
    take = r_blk < rmin
    return (jnp.where(take, r_blk, rmin),
            jnp.where(take, kloc.astype(jnp.int32) + offset, kstar))


def block_beta(b, cw_blk, s, dt=jnp.float32):
    """One shard's (U_b, D) beta tile from the decided b (eq. 44)."""
    bmat = cw_blk[:, None] * s[None, :]
    return (b[None, :] <= bmat * (1.0 + _TOL)).astype(dt)


def solve_rank1_sharded(h_w, k_i, w_prev_abs, eta, p_max,
                        c: LearningConstants, *, n_shards: int,
                        case: Case = Case.GD_CONVEX,
                        delta_prev: float = 0.0,
                        K_b: float | None = None) -> ShardedRank1:
    """The Theorem-4 rank-1 search, worker-sharded — logical execution.

    Drop-in twin of ``solve`` on a rank-1 channel (same argument
    conventions: ``k_i`` here is whatever the caller's solve would pass,
    e.g. the engine's k_eff), streaming the per-entry envelope in
    (U_b, D) tiles via ``lax.scan`` over the shard axis.  The result is
    bit-identical to ``_solve_rank1`` for every ``n_shards`` (see the
    section comment for the exactness argument); ``beta`` is NOT
    materialized — use ``block_beta`` per shard, or ``solve_sharded``
    when a full (U, D) beta is wanted for comparison.

    ``U % n_shards`` must be 0: callers pad the worker axis with inert
    workers (k_i = p_max = 0) first — padding is restriction-stable and
    never changes a real candidate (an inert worker's candidate is 0 and
    its k_eff mass is 0).
    """
    h_w = jnp.asarray(h_w)
    if h_w.ndim == 2:
        if h_w.shape[1] != 1:
            raise ValueError("sharded search is rank-1 only; got dense "
                             f"h of shape {h_w.shape}")
        h_w = h_w[:, 0]
    U = h_w.shape[0]
    if U % n_shards:
        raise ValueError(f"U={U} not divisible by n_shards={n_shards}; "
                         "pad the worker axis with inert workers first")
    u_b = U // n_shards
    w_prev_abs = jnp.asarray(w_prev_abs)
    dt = jnp.result_type(h_w.dtype, w_prev_abs.dtype, float)
    numer = case_numerator(case, k_i, c, delta_prev, K_b)
    k_arr = jnp.asarray(k_i, dt)
    k_eff = jnp.full_like(k_arr, K_b) if K_b is not None else k_arr
    cw, s = rank1_candidates(h_w, k_arr, p_max, w_prev_abs, eta, dt)
    cwb = cw.reshape(n_shards, u_b)
    thr_sorted, csum0 = jax.vmap(block_summary)(
        cwb, k_eff.reshape(n_shards, u_b))

    def body(_, cw_blk):
        den_blk = block_den(cw_blk, thr_sorted, csum0)
        return None, block_envelope(cw_blk, den_blk, s, c, numer)

    _, (rmin, kloc, cw_star) = jax.lax.scan(body, None, cwb)
    b, r, kstar = reduce_envelopes(rmin, kloc, cw_star, s, u_b)
    return ShardedRank1(b=b, r=r, kstar=kstar, cw=cwb, s=s)


def solve_sharded(h, k_i, w_prev_abs, eta, p_max, c: LearningConstants,
                  *, n_shards: int, case: Case = Case.GD_CONVEX,
                  delta_prev: float = 0.0,
                  K_b: float | None = None) -> InflotaSolution:
    """``solve`` computed via the sharded search — comparison/test entry.

    Assembles the full (U, D) beta from per-shard tiles, so use it only
    where (U, D) fits (equivalence tests, small-U inspection); the
    engine path streams ``block_beta`` tiles and never calls this.
    """
    sol = solve_rank1_sharded(h, k_i, w_prev_abs, eta, p_max, c,
                              n_shards=n_shards, case=case,
                              delta_prev=delta_prev, K_b=K_b)
    dt = sol.b.dtype
    beta = jnp.concatenate(
        [block_beta(sol.b, sol.cw[j], sol.s, dt)
         for j in range(n_shards)], axis=0)
    return InflotaSolution(b=sol.b, beta=beta, r=sol.r)


def solve_bucketed(h_workers, k_i, w_prev_abs, eta, p_max,
                   c: LearningConstants, n_buckets: int,
                   case: Case = Case.GD_CONVEX, delta_prev: float = 0.0,
                   K_b: float | None = None) -> InflotaSolution:
    """Beyond-paper granularity: share one (b, beta) across each bucket of
    entries.  The per-bucket |w| statistic takes the max over the bucket
    (conservative: keeps the power constraint (7) valid for every entry in
    the bucket), and the per-bucket channel gain is the per-worker scalar
    h_i (one coherent channel per worker per round, the common physical
    reading).  Reduces the search from O(D U^2) to O(n_buckets U^2) and the
    b/beta side-information from O(D) to O(n_buckets).

    Args:
      h_workers: (U,) per-worker channel gains (scalar channel per round).
    Returns an InflotaSolution over buckets: b (n_buckets,),
    beta (U, n_buckets).  Use `jnp.repeat` / reshape upstream to expand.
    """
    D = w_prev_abs.shape[0]
    pad = (-D) % n_buckets
    w_pad = jnp.pad(w_prev_abs, (0, pad))
    w_stat = jnp.max(jnp.abs(w_pad).reshape(n_buckets, -1), axis=1)
    # rank-1: solve broadcasts the per-worker scalar gain internally
    return solve(jnp.asarray(h_workers)[:, None], k_i, w_stat, eta, p_max,
                 c, case, delta_prev, K_b)
