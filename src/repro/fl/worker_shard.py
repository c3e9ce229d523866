"""Worker-sharded OTA round engine — million-worker rounds without (U, D).

The dense engine (``fl/engine.py``) materializes the full (U, D) block of
local updates each round, capping U at what one device holds.  This tier
partitions the worker axis into ``S = FLConfig.worker_sharding``
contiguous blocks of ``U_b = U / S`` workers and streams the round in
(U_b, D) tiles: local updates, the Theorem-4 search (via the sharded
sorted-prefix solver in ``core/inflota.py``) and the analog transmit all
run per block, and only (D,) partial superpositions / reductions ever
cross blocks.  No intermediate of the round has U * D elements — pinned
by a jaxpr-shape inspection in ``tests/test_worker_sharded.py``.

Two execution modes:

  * logical (``mesh=None``): one ``jax.lax.scan`` over the S blocks on
    whatever device runs the step.  This is the CANONICAL mode and the
    one sweep cohorts use (the sweep engine keeps the device mesh for
    the experiment axis): values depend only on the logical shard count
    S, never on the device count, so a 4-device experiment-sharded
    sweep of a ``U_shards`` grid stays byte-identical to the 1-device
    run on CPU — the store identity the multi-device test asserts (on a
    TPU, see docs/sweeps.md, Multi-device).
  * mesh (``mesh=worker_mesh()``): ``shard_map`` over the ``'data'``
    FL-worker axis of ``sharding/specs.py`` — each device scans its
    S / n_devices blocks; per-shard search summaries and (D,) transmit
    partials cross devices via tiled ``all_gather`` (order-preserving,
    so the combine below is the same fixed-order ``jnp.sum`` over the
    stacked (S, D) partials in both modes).  Mesh mode mirrors logical
    mode op for op, but it is a DIFFERENT compiled program, and XLA's
    elementwise fusion may contract an fma differently on some inputs
    — so mesh matches logical within f32 reassociation tolerance
    (ulp-level per round in practice), not bit-for-bit.  Anything that
    must be byte-stable (sweep stores) therefore runs logical mode.

Exactness tiers against the dense engine (``tests/test_worker_sharded*``):

  * ``worker_sharding = 1`` (jnp backend): BIT-EXACT — the single block
    reproduces the dense op order end to end.
  * ``worker_sharding = S > 1``: the Theorem-4 decision (b, beta,
    selected set) and every integer-valued reduction (den_keff, den_ki,
    sel) stay bit-exact (integer f32 sums reassociate exactly below
    2^24); only the received superposition ``y = sum_i tx_i h_i``
    reassociates, so ``round_step`` matches within f32 tolerance.
  * per-worker randomness (channel draws, local-update keys, minibatch
    draws) is restriction-stable ``fold_in``-by-global-index
    (``core/channel.worker_keys``), so every worker draws the same
    stream under ANY repartition — including the inert padding added
    when S does not divide U (refused for channel models that are not
    ``ragged_exact``, where padding would shift the draws).

Backends: the jnp path is the reference; ``backend="pallas"`` streams
each block's transmit through the fused ``kernels.ota_shard_tx`` tile
kernel (beta is rebuilt in VMEM from the decided b and never written to
HBM).  The Theorem-4 SEARCH always runs the canonical jnp sharded solver
— so the sharded pallas path matches the sharded/dense JNP decision
bit-exactly, while dense-pallas (whose in-kernel search orders the
candidate arithmetic differently) agrees only within tolerance.
Non-inflota policies keep worker-level (U, 1) decisions; their transmit
runs per block in jnp under either backend.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from repro.core import channel as chan
from repro.core import convergence as conv
from repro.core import inflota
from repro.core import power as power_lib
from repro.core import selection as selection_lib
from repro.core.objectives import case_numerator
from repro.fl import engine as engine_lib
from repro.fl.client import local_update_masked

_EPS = 1e-12


def worker_mesh(n: Optional[int] = None):
    """A 1-D device mesh over the ``'data'`` FL-worker axis.

    Returns None when one device is visible (the logical path needs no
    mesh).  ``FLConfig.worker_sharding`` must be a multiple of the mesh's
    ``'data'`` size: each device then scans S / n_devices blocks.
    """
    avail = len(jax.devices())
    n = avail if n is None else min(n, avail)
    if n <= 1:
        return None
    from repro.launch import mesh as mesh_lib
    return mesh_lib.make_smoke_mesh(data=n, model=1)


def _pad_axis0(a, n: int):
    pad = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def _blocked(a, s: int):
    return a.reshape((s, a.shape[0] // s) + a.shape[1:])


def build_sharded_engine(task, X, Y, mask, k_i, cfg, params0,
                         wmask: Optional[jax.Array] = None,
                         mesh=None, mesh_axis: str = "data"
                         ) -> "engine_lib.Engine":
    """Worker-sharded twin of ``engine.build_engine`` (same Engine API).

    ``build_engine`` delegates here when ``cfg.worker_sharding`` is set;
    call directly to run the round on a worker mesh (``mesh=`` a
    ``worker_mesh()``; the sweep engine always passes None and keeps its
    mesh for the experiment axis).

    When S does not divide U the worker axis is padded with inert
    workers (zero samples, zero power): restriction-stable randomness
    plus the masked-worker guarantees of the dense engine make the
    padding exact for the search and the reductions; only the block
    boundaries (hence the f32 reassociation of y) shift.
    """
    cfg_s = int(cfg.worker_sharding)
    if cfg_s < 1:
        raise ValueError(f"worker_sharding must be >= 1: {cfg.worker_sharding}")
    S = cfg_s
    flat0, unravel = ravel_pytree(params0)
    D = flat0.shape[0]
    U0 = k_i.shape[0]
    backend = cfg.resolved_backend()
    policy = cfg.resolved_policy()

    if cfg.k_b is not None and not isinstance(mask, jax.core.Tracer):
        # same up-front minibatch guard as build_engine, against the
        # PRE-padding mask (inert padded workers legitimately have 0)
        min_k = int(np.min(np.sum(np.asarray(mask), axis=1)))
        if cfg.k_b > min_k:
            raise ValueError(
                f"k_b={cfg.k_b} exceeds the smallest worker's sample "
                f"count ({min_k}); minibatch sampling would draw padding")

    u_b = -(-U0 // S)
    U = S * u_b
    if U != U0:
        if not chan.ragged_exact(cfg.channel_model):
            raise ValueError(
                f"worker_sharding={S} does not divide U={U0} and channel "
                f"model {cfg.channel_model!r} is not restriction-stable "
                "under worker padding; pick a divisor of U")
        X, Y, mask = (_pad_axis0(a, U) for a in (X, Y, mask))
        k_i = _pad_axis0(k_i, U)
        base = jnp.ones((U0,), jnp.float32) if wmask is None else wmask
        wmask = jnp.concatenate([base, jnp.zeros((U - U0,), jnp.float32)])

    model = cfg.resolved_channel_model(U)
    k_eff = (jnp.full((U,), float(cfg.k_b), jnp.float32)
             if cfg.k_b is not None else k_i)
    p_max = jnp.full((U,), cfg.channel.p_max, jnp.float32)
    if wmask is not None:
        k_i = k_i * wmask
        k_eff = k_eff * wmask
        p_max = p_max * wmask
    c = cfg.constants

    if mesh is not None:
        ndev = dict(mesh.shape)[mesh_axis]
        if S % ndev:
            raise ValueError(
                f"worker_sharding={S} must be a multiple of the mesh's "
                f"'{mesh_axis}' axis size ({ndev})")

    # static per-worker operands, shard-blocked once at build time
    blocked_const = {
        "X": _blocked(X, S), "Y": _blocked(Y, S),
        "mask": _blocked(mask, S), "k_eff": _blocked(k_eff, S),
        "k_i": _blocked(k_i, S), "p_max": _blocked(p_max, S),
    }
    if wmask is not None:
        blocked_const["wmask"] = _blocked(wmask, S)

    is_inflota = isinstance(policy, selection_lib.InflotaPolicy)
    exact = getattr(policy, "exact", False)
    n_real = (jnp.float32(U) if wmask is None else jnp.sum(wmask))

    def local_block(w_prev, xs):
        """(U_b, D) local updates for one shard block."""
        params = unravel(w_prev)
        return jax.vmap(
            lambda x, y, m, k: ravel_pytree(local_update_masked(
                task, params, x, y, m, cfg.lr, key=k, k_b=cfg.k_b))[0]
        )(xs["X"], xs["Y"], xs["mask"], xs["keys"])

    def tx_parts(Wb, beta_blk, xs, b):
        """One block's (D,) transmit partials — jnp reference ops,
        mirroring ``aggregation.ota_aggregate`` so S = 1 is bit-exact."""
        tx = power_lib.tx_signal(Wb, beta_blk, xs["k_eff"], b,
                                 xs["h_est"][:, None], xs["p_max"])
        y_p = jnp.sum(tx * xs["h"][:, None], axis=0)
        denk = jnp.broadcast_to(
            jnp.sum(xs["k_eff"][:, None] * beta_blk, axis=0), (D,))
        deni = jnp.broadcast_to(
            jnp.sum(xs["k_i"][:, None] * beta_blk, axis=0), (D,))
        sel = jnp.broadcast_to(jnp.sum(beta_blk, axis=0), (D,))
        return y_p, denk, deni, sel

    def core(sharded, repl, *, gather):
        """The blocked round body: search (entry-level policies) + blocked
        transmit.  Runs once over all S blocks (logical mode) or once per
        device over its S_local blocks under ``shard_map`` (mesh mode) —
        ``gather`` is identity or a tiled all_gather along the worker
        axis.  Every cross-block value is (U,)- or (S, D)-sized.
        """
        if is_inflota:
            th, cs = gather(jax.vmap(inflota.block_summary)(
                sharded["cw"], sharded["k_den"]))
            sstat = repl["s"]

            def sbody(_, cw_blk):
                den_blk = inflota.block_den(cw_blk, th, cs)
                return None, inflota.block_envelope(
                    cw_blk, den_blk, sstat, policy.constants,
                    repl["numer_pol"])

            _, env = jax.lax.scan(sbody, None, sharded["cw"])
            rmin, kloc, cw_star = gather(env)
            b, _, _ = inflota.reduce_envelopes(rmin, kloc, cw_star,
                                               sstat, u_b)
        else:
            b = repl["b"]

        def tbody(_, xs):
            Wb = local_block(repl["w_prev"], xs)
            if is_inflota:
                if backend is engine_lib.Backend.PALLAS:
                    from repro.kernels import ops as kops
                    return None, kops.ota_shard_tx(
                        Wb, xs["h"], xs["h_est"], xs["cw"], repl["s"], b,
                        xs["k_eff"], xs["k_i"], xs["p_max"],
                        wmask=xs.get("wmask"))
                beta_blk = inflota.block_beta(b, xs["cw"], repl["s"],
                                              b.dtype)
                if "wmask" in xs:
                    beta_blk = beta_blk * xs["wmask"][:, None]
            else:
                beta_blk = xs["beta"][:, None]
            return None, tx_parts(Wb, beta_blk, xs, b)

        _, parts = jax.lax.scan(tbody, None, sharded)
        return gather(parts), b

    def combine(parts, b, noise, w_prev, delta_prev):
        """Fixed-order reduction of the (S, D) partial stacks + the
        post-processing / bookkeeping of ``build_ota_stage`` — shared by
        both execution modes (the mesh path all_gathers the same stacks
        first), so values never depend on the device count."""
        ys, denks, denis, sels = parts
        y = jnp.sum(ys, axis=0) + noise
        den_keff = jnp.sum(denks, axis=0) * b
        den_ki = jnp.sum(denis, axis=0)
        sel = jnp.sum(sels, axis=0)
        w_hat = jnp.where(den_keff > _EPS,
                          y / jnp.maximum(den_keff, _EPS), 0.0)
        new_flat = jnp.where(den_keff > _EPS, w_hat, w_prev)
        a_t = conv.A_t_from_den(den_ki, k_i, c)
        b_t = conv.B_t_from_den(den_ki, b, k_i, c)
        delta = b_t + a_t * delta_prev
        # pinned_mean + reciprocal-multiply: fixed accumulation order
        # and a division XLA lowers exactly in every program context, so
        # the snr scalar stays byte-stable across compiled programs
        # (device counts, batch padding) — see repro.fl.engine.pinned_mean
        noise_pow = c.sigma2 * engine_lib.pinned_mean(
            1.0 / jnp.maximum(den_ki * b, _EPS) ** 2)
        snr = engine_lib.pinned_mean(new_flat ** 2) * (
            1.0 / jnp.maximum(noise_pow, _EPS))
        return new_flat, delta, sel, b, a_t, b_t, snr

    def step(state: "engine_lib.RoundState", _=None):
        key_next, klocal, kchan, kpol = jax.random.split(state.key, 4)
        w_prev = state.flat

        if exact:
            # error-free oracle: blocked exact weighted FedAvg
            keys = chan.worker_keys(klocal, U)
            sharded = {**blocked_const, "keys": _blocked(keys, S)}

            def fcore(sh, repl, *, gather):
                def fbody(_, xs):
                    Wb = local_block(repl["w_prev"], xs)
                    return None, jnp.sum(
                        xs["k_i"][:, None].astype(Wb.dtype) * Wb, axis=0)
                _, nums = jax.lax.scan(fbody, None, sh)
                return gather(nums)

            nums = _dispatch(fcore, sharded, {"w_prev": w_prev})
            new_flat = (jnp.sum(nums, axis=0)
                        / jnp.sum(k_i.astype(nums.dtype)))
            new_state = engine_lib.RoundState(
                flat=new_flat, w_prev2=w_prev, delta=state.delta,
                t=state.t + 1, key=key_next, chan=state.chan)
            return new_state, engine_lib.RoundStats(
                selected=n_real, b_mean=jnp.float32(0.0),
                a_t=jnp.float32(1.0 - c.mu / c.L), b_t=jnp.float32(0.0),
                eta=jnp.float32(0.0), snr=jnp.float32(0.0))

        kg, kn = chan.round_keys(kchan, state.t)
        chan_carry, h_true = model.step(state.chan, kg, state.t)
        h_est = model.estimate(h_true, chan.estimate_key(kg))
        noise = chan.sample_noise(kn, (D,), cfg.channel)
        eta = jnp.abs(w_prev - state.w_prev2) + 1e-8
        keys = chan.worker_keys(klocal, U)
        sharded = {**blocked_const, "keys": _blocked(keys, S),
                   "h": _blocked(h_true, S), "h_est": _blocked(h_est, S)}
        repl: dict = {"w_prev": w_prev}

        if is_inflota:
            # mirror InflotaPolicy.decide -> inflota.solve exactly: the
            # search sees the CSI estimate, k_eff as solve's k_i, and the
            # policy's own constants/case/K_b for the numerator
            w_abs = jnp.abs(w_prev)
            dt = jnp.result_type(h_est.dtype, w_abs.dtype, float)
            numer_pol = case_numerator(policy.case, k_eff,
                                       policy.constants, state.delta,
                                       policy.K_b)
            k_den = (jnp.full_like(jnp.asarray(k_eff, dt), policy.K_b)
                     if policy.K_b is not None else k_eff.astype(dt))
            cw, sstat = inflota.rank1_candidates(h_est, k_eff, p_max,
                                                 w_abs, eta, dt)
            # NB: "k_den" (the search's den weights, K_b-substituted like
            # solve's) is distinct from "k_eff" (the engine's transmit /
            # den_keff weights) — the two coincide only because the
            # registry builds InflotaPolicy with K_b = cfg.k_b
            sharded = {**sharded, "cw": _blocked(cw, S),
                       "k_den": _blocked(k_den, S)}
            repl.update(s=sstat, numer_pol=numer_pol)
        else:
            numer = case_numerator(cfg.case, k_i, c, state.delta,
                                   cfg.k_b)
            ctx = selection_lib.PolicyContext(
                h_est=h_est, w_prev_abs=jnp.abs(w_prev), eta=eta,
                k_eff=k_eff, k_i=k_i, p_max=p_max, numer=numer,
                delta_prev=state.delta, t=state.t, wmask=wmask)
            dec = policy.decide(kpol, ctx)
            if dec.beta.ndim != 2 or dec.beta.shape[1] != 1:
                raise ValueError(
                    "worker-sharded rounds support entry-level selection "
                    "only for the inflota policy; got a "
                    f"{dec.beta.shape} beta from {type(policy).__name__}")
            sharded = {**sharded, "beta": _blocked(dec.beta[:, 0], S)}
            repl["b"] = dec.b

        parts, b = _dispatch(core, sharded, repl)
        new_flat, delta, sel, b, a_t, b_t, snr = combine(
            parts, b, noise, w_prev, state.delta)
        new_state = engine_lib.RoundState(
            flat=new_flat, w_prev2=w_prev, delta=delta, t=state.t + 1,
            key=key_next, chan=chan_carry)
        return new_state, engine_lib.RoundStats(
            selected=jnp.mean(sel), b_mean=jnp.mean(b), a_t=a_t, b_t=b_t,
            eta=jnp.mean(eta), snr=snr)

    def _dispatch(fn, sharded, repl):
        """Run a blocked body logically or under shard_map on the mesh."""
        if mesh is None:
            return fn(sharded, repl, gather=lambda x: x)

        def ag(x):
            return jax.tree.map(
                lambda v: jax.lax.all_gather(v, mesh_axis, axis=0,
                                             tiled=True), x)

        return jax.shard_map(functools.partial(fn, gather=ag), mesh=mesh,
                             in_specs=(P(mesh_axis), P()), out_specs=P(),
                             check_vma=False)(sharded, repl)

    def init(flat: jax.Array, key: jax.Array) -> "engine_lib.RoundState":
        carry = model.init_state(jax.random.fold_in(key, 0x636861))
        return engine_lib.init_state(flat, key, chan_carry=carry)

    return engine_lib.Engine(step=step, unravel=unravel, D=D, init=init)
