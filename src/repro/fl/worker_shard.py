"""Worker-sharded OTA round engine — million-worker rounds without (U, D).

The dense engine (``fl/engine.py``) materializes the full (U, D) block of
local updates each round, capping U at what one device holds.  This tier
partitions the worker axis into ``S = FLConfig.worker_sharding``
contiguous blocks of ``U_b = U / S`` workers and streams the round in
(U_b, D) tiles: local updates, the Theorem-4 search (via the sharded
sorted-prefix solver in ``core/inflota.py``) and the analog transmit all
run per block, and only running (D,) values cross blocks: the search's
first-min (rmin, kstar), the superposition y and, one at a time after
it, the three per-entry beta reductions.  No intermediate of the round
has U * D or S * D elements — pinned by a jaxpr-shape inspection in
``tests/test_worker_sharded.py`` — so a local model of D = 5.7e8
(Moonlight-16B-A3B's one-chip share) runs one worker a block on one
16 GB chip.

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
    S / n_devices blocks; the per-shard search summaries cross devices
    via a tiled ``all_gather``, each device's running first-min reduces
    by ``pmin`` to the global first-min (exact), and its (D,) running
    sums by ``psum``.  Mesh mode mirrors logical mode op for op, but it
    is a DIFFERENT compiled program and its psum regroups y, so mesh
    matches logical within f32 reassociation tolerance (ulp-level per
    round in practice), not bit-for-bit.  Anything that must be
    byte-stable (sweep stores) therefore runs logical mode.

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
HBM; the block's signal is added onto the carried y in place).  The
Theorem-4 SEARCH always runs the canonical jnp sharded solver — so the
sharded pallas path matches the sharded/dense JNP decision
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
from jax.sharding import PartitionSpec as P

from repro.core import channel as chan
from repro.core import convergence as conv
from repro.core import inflota
from repro.core import power as power_lib
from repro.core import selection as selection_lib
from repro.core.objectives import case_numerator
from repro.fl import engine as engine_lib
from repro.fl.client import local_grad_masked
from repro.obs import metrics as metrics_lib

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


def _unraveler(params0):
    """(D, unravel, spans) of a parameter tree of arrays or
    ``jax.ShapeDtypeStruct``s, in ``ravel_pytree``'s order: a model of
    D = 5.7e8 never builds a flat template.  ``spans`` lists each leaf's
    (offset, size) in the flat vector; ``unravel`` cuts the leaves out
    with static slices."""
    leaves, treedef = jax.tree.flatten(params0)
    sizes = [int(np.prod(leaf.shape)) for leaf in leaves]
    offsets = [int(o) for o in np.cumsum([0] + sizes)]

    def unravel(flat):
        return jax.tree.unflatten(treedef, [
            jax.lax.slice(flat, (o,), (o + n,)).reshape(
                leaf.shape).astype(leaf.dtype)
            for leaf, o, n in zip(leaves, offsets, sizes)])

    return offsets[-1], unravel, list(zip(offsets, sizes))


def _stat(w_prev, w_prev2, dt):
    """The Theorem-4 statistic s = 1 / (|w_{t-1}| + eta) with eta =
    |w_{t-1} - w_{t-2}| + 1e-8 (footnote 4), op for op as the round's
    own ``eta`` and ``inflota.rank1_candidates`` compute it."""
    return (1.0 / (jnp.abs(w_prev) + (jnp.abs(w_prev - w_prev2) + 1e-8))
            ).astype(dt)


def _pad_axis0(a, n: int):
    pad = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def _blocked(a, s: int):
    return a.reshape((s, a.shape[0] // s) + a.shape[1:])


class _Logical:
    """Cross-block operations of logical mode: every block is local."""

    @staticmethod
    def gather(x):
        return x

    @staticmethod
    def first_min(rmin, kstar):
        return kstar

    @staticmethod
    def sum(x):
        return x


_LOGICAL = _Logical()


class _MeshColl:
    """Cross-block operations of mesh mode, over the devices' contiguous
    block ranges: the per-worker search summaries are all-gathered in
    worker order; the running first-min (rmin, kstar) of each device
    reduces to the lowest global index attaining the global min, which
    is the first-min over every block (min is exact); (D,) sums reduce
    by psum, exact for the integer-valued beta sums and within f32
    reassociation for the superposition."""

    def __init__(self, axis: str):
        self.axis = axis

    def gather(self, x):
        return jax.tree.map(lambda v: jax.lax.all_gather(
            v, self.axis, axis=0, tiled=True), x)

    def first_min(self, rmin, kstar):
        g = jax.lax.pmin(rmin, self.axis)
        return jax.lax.pmin(jnp.where(rmin == g, kstar,
                                      jnp.iinfo(jnp.int32).max), self.axis)

    def sum(self, x):
        return jax.lax.psum(x, self.axis)


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
    D, unravel, spans = _unraveler(params0)
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

    reg = metrics_lib.process_registry()
    reg.gauge("worker_shard_block_workers",
              "workers per block of the last worker-sharded engine "
              "built").set(u_b)
    reg.gauge("worker_shard_block_bytes",
              "bytes of one block's (U_b, D) f32 local updates").set(
                  4 * u_b * D)

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
    block_ids = jnp.arange(S, dtype=jnp.int32)

    is_inflota = isinstance(policy, selection_lib.InflotaPolicy)
    exact = getattr(policy, "exact", False)
    n_real = (jnp.float32(U) if wmask is None else jnp.sum(wmask))

    def fold_updates(w_prev, xs, carry, fn):
        """Fold ``fn(carry, offset, size, W)`` over one block's local
        updates leaf by leaf in ravel order, W the leaf's (U_b, size)
        updated values.  No (U_b, D) array is assembled: each leaf's
        update is made only once the previous leaf's ``fn`` has returned
        its carry (an optimization barrier orders them), so a block
        holds its gradient and one leaf's update beside the carried (D,)
        values."""
        params = unravel(w_prev)
        grads = jax.vmap(lambda x, y, m, k: local_grad_masked(
            task, params, x, y, m, key=jax.random.split(k, 1)[0],
            k_b=cfg.k_b))(xs["X"], xs["Y"], xs["mask"], xs["keys"])
        for (o, n), p, g in zip(spans, jax.tree.leaves(params),
                                jax.tree.leaves(grads)):
            p, g, carry = jax.lax.optimization_barrier((p, g, carry))
            carry = fn(carry, o, n, (p - cfg.lr * g).reshape(g.shape[0], n))
        return carry

    def beta_tile(xs, b, s):
        """One block's selection over the entries of ``b`` / ``s``: the
        (U_b, n) eq.-44 tile of the decided b (inflota), or the policy's
        (U_b, 1) worker-level beta."""
        if not is_inflota:
            return xs["beta"][:, None]
        beta = inflota.block_beta(b, xs["cw"], s, b.dtype)
        if "wmask" in xs:
            beta = beta * xs["wmask"][:, None]
        return beta

    def search(sharded, repl, *, coll):
        """The Theorem-4 winner per entry (global worker index): every
        block's envelope folded into a running first-min, so only (D,)
        values cross blocks."""
        th, cs = coll.gather(jax.vmap(inflota.block_summary)(
            sharded["cw"], sharded["k_den"]))
        sstat = repl["s"]

        def sbody(carry, xs):
            den_blk = inflota.block_den(xs["cw"], th, cs)
            return inflota.envelope_step(
                carry, xs["cw"], den_blk, sstat, policy.constants,
                repl["numer_pol"], xs["bidx"] * u_b), None

        init = (jnp.full(sstat.shape, jnp.inf, sstat.dtype),
                jnp.zeros(sstat.shape, jnp.int32))
        (rmin, kstar), _ = jax.lax.scan(
            sbody, init, {"cw": sharded["cw"], "bidx": sharded["bidx"]})
        return coll.first_min(rmin, kstar)

    def transmit(sharded, repl, *, coll):
        """Local updates and transmit, block by block and leaf by leaf,
        each leaf's received signal added onto its span of the carried
        superposition (jnp ops mirroring ``aggregation.ota_aggregate``,
        so S = 1 is bit-exact, or the ``ota_shard_tx`` kernel)."""
        def tbody(y, xs):
            # fenced with the carried y, the loop's invariants read as
            # new values each block, so XLA keeps the per-leaf work (the
            # leaf's params, s and b spans) inside the loop instead of
            # hoisting all of it out at once, which would hold a second
            # (D,) copy through the whole transmit
            y, b, w_prev, w_prev2 = jax.lax.optimization_barrier(
                (y, repl["b"], repl["w_prev"], repl["w_prev2"]))

            def leaf_tx(y, o, n, W):
                with jax.named_scope("ota.transmit"):
                    b_l = b[o:o + n]
                    # the leaf's s, remade from its spans of w_{t-1} and
                    # w_{t-2}: no (D,) s is held through the transmit
                    s_l = (_stat(w_prev[o:o + n], w_prev2[o:o + n],
                                 b.dtype) if is_inflota else None)
                    if is_inflota and backend is engine_lib.Backend.PALLAS:
                        from repro.kernels import ops as kops
                        y_l = kops.ota_shard_tx(
                            W, xs["h"], xs["h_est"], xs["cw"], s_l, b_l,
                            xs["k_eff"], xs["k_i"], xs["p_max"],
                            xs.get("wmask"), y=y[o:o + n])
                    else:
                        tx = power_lib.tx_signal(
                            W, beta_tile(xs, b_l, s_l), xs["k_eff"], b_l,
                            xs["h_est"][:, None], xs["p_max"])
                        y_l = y[o:o + n] + jnp.sum(tx * xs["h"][:, None],
                                                   axis=0)
                    return jax.lax.dynamic_update_slice(y, y_l, (o,))

            return fold_updates(w_prev, xs, y, leaf_tx), None

        with jax.named_scope("fl.local_update"):
            y, _ = jax.lax.scan(tbody, jnp.zeros((D,), repl["w_prev"].dtype),
                                sharded)
        return coll.sum(y)

    def beta_sum(weight: Optional[str]):
        """Blocked body of one per-entry beta reduction, sum_i w_i
        beta_i (w = ``weight``'s per-worker values, or 1), accumulated
        in block order; integer-valued, so exact in any order."""
        def body(sharded, repl, *, coll):
            def dbody(acc, xs):
                beta = beta_tile(xs, repl["b"], repl.get("s"))
                if weight is not None:
                    beta = xs[weight][:, None] * beta
                return acc + jnp.sum(beta, axis=0), None

            acc, _ = jax.lax.scan(dbody, jnp.zeros((D,), repl["b"].dtype),
                                  sharded)
            return coll.sum(acc)
        return body

    def combine(y, b, sharded, repl, delta_prev):
        """Descale and bookkeeping of ``build_ota_stage``, shared by both
        execution modes.  The three beta reductions are computed one at
        a time after y, each fenced behind the last value it may not
        outlive, so at most one of them is live beside y; their s is
        remade after y, so the search's s is not held through the
        transmit."""
        y, b, w_prev, w_prev2 = jax.lax.optimization_barrier(
            (y, b, repl["w_prev"], repl["w_prev2"]))
        repl = {**repl, "s": (_stat(w_prev, w_prev2, b.dtype)
                              if is_inflota else None)}
        den_keff = _dispatch(beta_sum("k_eff"), sharded, {**repl, "b": b}) * b
        w_hat = jnp.where(den_keff > _EPS,
                          y / jnp.maximum(den_keff, _EPS), 0.0)
        new_flat = jnp.where(den_keff > _EPS, w_hat, w_prev)
        new_flat, b = jax.lax.optimization_barrier((new_flat, b))
        den_ki = _dispatch(beta_sum("k_i"), sharded, {**repl, "b": b})
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
        snr, b = jax.lax.optimization_barrier((snr, b))
        sel = _dispatch(beta_sum(None), sharded, {**repl, "b": b})
        return new_flat, delta, jnp.mean(sel), a_t, b_t, snr

    def step(state: "engine_lib.RoundState", _=None):
        key_next, klocal, kchan, kpol = jax.random.split(state.key, 4)
        w_prev = state.flat

        if exact:
            # error-free oracle: blocked exact weighted FedAvg
            keys = chan.worker_keys(klocal, U)
            sharded = {**blocked_const, "keys": _blocked(keys, S)}

            def fcore(sh, repl, *, coll):
                def fbody(acc, xs):
                    def leaf_sum(acc, o, n, W):
                        return jax.lax.dynamic_update_slice(
                            acc, acc[o:o + n] + jnp.sum(
                                xs["k_i"][:, None].astype(W.dtype) * W,
                                axis=0), (o,))
                    return fold_updates(repl["w_prev"], xs, acc,
                                        leaf_sum), None
                with jax.named_scope("fl.local_update"):
                    num, _ = jax.lax.scan(
                        fbody, jnp.zeros((D,), repl["w_prev"].dtype), sh)
                return coll.sum(num)

            num = _dispatch(fcore, sharded, {"w_prev": w_prev})
            new_flat = num / jnp.sum(k_i.astype(num.dtype))
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
        repl: dict = {"w_prev": w_prev, "w_prev2": state.w_prev2}

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
                       "k_den": _blocked(k_den, S), "bidx": block_ids}
            repl["numer_pol"] = numer_pol
            with jax.named_scope("inflota.search"):
                kstar = _dispatch(search, sharded, {**repl, "s": sstat})
                repl["b"] = jnp.take(cw, kstar) * sstat
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

        y = _dispatch(transmit, sharded, repl) + noise
        with jax.named_scope("ota.combine"):
            new_flat, delta, sel, a_t, b_t, snr = combine(
                y, repl["b"], sharded, repl, state.delta)
        new_state = engine_lib.RoundState(
            flat=new_flat, w_prev2=w_prev, delta=delta, t=state.t + 1,
            key=key_next, chan=chan_carry)
        return new_state, engine_lib.RoundStats(
            selected=sel, b_mean=jnp.mean(repl["b"]), a_t=a_t, b_t=b_t,
            eta=jnp.mean(eta), snr=snr)

    def _dispatch(fn, sharded, repl):
        """Run a blocked body logically or under shard_map on the mesh."""
        if mesh is None:
            return fn(sharded, repl, coll=_LOGICAL)
        return jax.shard_map(functools.partial(fn, coll=_MeshColl(mesh_axis)),
                             mesh=mesh, in_specs=(P(mesh_axis), P()),
                             out_specs=P(), check_vma=False)(sharded, repl)

    def init(flat: jax.Array, key: jax.Array) -> "engine_lib.RoundState":
        carry = model.init_state(jax.random.fold_in(key, 0x636861))
        return engine_lib.init_state(flat, key, chan_carry=carry)

    return engine_lib.Engine(step=step, unravel=unravel, D=D, init=init)
