"""Fused single-pass OTA round engine — jit/scan-compatible Algorithm 1.

One pure functional ``round_step(state, _) -> (state, stats)`` drives every
scenario: the engine is generic over two small interfaces instead of
branching on config strings.

  * ``ChannelModel`` (``repro.core.channel``) produces the true per-worker
    gains each round (``step``) and the CSI estimate the PS observes
    (``estimate``); its carry threads through ``RoundState.chan``, so
    time-correlated fading (``GaussMarkovFading``) and imperfect CSI
    (``ImperfectCSI``) run inside one ``jax.lax.scan`` with zero per-round
    recompiles.
  * ``RoundPolicy`` (``repro.core.selection``) turns the estimate into a
    structured ``PolicyDecision(b, beta, reductions, sel)``.  Both
    backends consume the decision: the A_t/B_t bookkeeping reads only the
    ``BetaReductions``, never beta itself.  Policies expose two
    capabilities the engine checks structurally (no name matching):
    ``exact`` (error-free oracle -> exact FedAvg, e.g. PerfectPolicy) and
    ``fused_stage(backend)`` (whole-stage override — InflotaPolicy
    returns the single-VMEM-pass ``kernels.ota_round`` for "pallas").

Strings still work everywhere: ``FLConfig(policy="inflota",
channel_model="gauss_markov")`` resolves through the registries in
``selection`` / ``channel``; instances pass straight through, so a new
policy or channel model defined in a test plugs in without touching this
file.

Design points carried over from the fused engine:

  * Local updates are vmap-batched: worker datasets are padded to a
    uniform K_max with sample masks (``client.local_update_masked``), so
    one dispatch covers all U workers instead of U serial jitted calls.
  * The channel is RANK-1 (scalar-per-worker) end to end — neither
    backend ever materializes the broadcast (U, D) matrix in HBM.
  * Both backends take traced ``eta`` / ``numer`` / ``t`` / gains /
    estimates, so the whole step compiles once — no per-round recompiles
    or host syncs.
  * The step is a valid ``jax.lax.scan`` body: ``FLTrainer.run`` uses a
    scan for small-D workloads and a Python loop (same jitted step) when
    per-round host-side eval is wanted.
"""

from __future__ import annotations

import dataclasses
import enum
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import aggregation as agg
from repro.core import channel as chan
from repro.core import convergence as conv
from repro.core import selection as selection_lib
from repro.core.channel import ChannelConfig
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case, case_numerator
from repro.fl.client import local_update_masked
from repro.kernels import ops as kops

_EPS = 1e-12


def pinned_mean(x: jax.Array) -> jax.Array:
    """Mean of a 1-D array with a FIXED accumulation order.

    ``jnp.mean`` lowers to an XLA ``reduce`` whose float accumulation
    order is implementation-defined — it shifts with the surrounding
    program (experiment-batch padding, SPMD partitioning), which moves
    scalar telemetry like ``RoundStats.snr`` at ulp level between
    compiled programs that must produce byte-identical stores (the
    sweep's device-count invariance).  Explicit elementwise adds are
    never reassociated by XLA, so folding zero-padded halves pins the
    value to the logical shape alone.  O(log D) extra ops; used only
    for per-round scalar bookkeeping, never on the U- or D-hot path.
    """
    x = x.reshape(-1)
    n = x.shape[0]
    m = 1
    while m < n:
        m *= 2
    if m != n:  # +0.0 padding is exact: a + 0.0 == a for finite a
        x = jnp.concatenate([x, jnp.zeros((m - n,), x.dtype)])
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0] / n


class Backend(enum.Enum):
    """Which implementation computes the OTA policy + aggregation."""
    AUTO = "auto"        # pallas iff cfg.use_kernels (legacy switch)
    JNP = "jnp"          # pure-jnp reference path
    PALLAS = "pallas"    # fused single-pass kernels.ota_round


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Scenario + training configuration for the OTA-FL round engine.

    ``policy`` and ``channel_model`` each accept a registry name (str) or
    a constructed instance (``RoundPolicy`` / ``ChannelModel``);
    ``channel_model=None`` builds the paper-faithful iid model from
    ``channel`` (``ExpIID``, or ``RayleighAmplitude`` when
    ``channel.amplitude``).
    """

    rounds: int = 100
    lr: float = 0.01
    policy: Any = "inflota"           # name | RoundPolicy instance
    case: Case = Case.GD_CONVEX
    k_b: Optional[int] = None         # mini-batch size (SGD); None = full GD
    channel: ChannelConfig = ChannelConfig()
    channel_model: Any = None         # None | name | ChannelModel instance
    constants: LearningConstants = LearningConstants()
    select_prob: float = 0.5          # random policy
    use_kernels: bool = False         # DEPRECATED: use backend=Backend.PALLAS
    backend: Backend | str = Backend.AUTO
    scan: bool = False                # run() via one jax.lax.scan
    eval_every: int = 1
    seed: int = 0
    worker_sharding: Optional[int] = None  # S shard blocks over workers;
    # None = dense (U, D) engine.  See fl/worker_shard.py for semantics
    # (S=1 is bit-exact vs dense; S>1 within f32 reassociation tolerance
    # with a bit-exact Theorem-4 decision).

    def resolved_backend(self) -> Backend:
        b = Backend(self.backend) if not isinstance(self.backend, Backend) \
            else self.backend
        if self.use_kernels:
            warnings.warn(
                "FLConfig.use_kernels is deprecated; pass "
                "backend=Backend.PALLAS (or backend='pallas') instead",
                DeprecationWarning, stacklevel=2)
        if b is Backend.AUTO:
            return Backend.PALLAS if self.use_kernels else Backend.JNP
        return b

    def resolved_policy(self) -> selection_lib.RoundPolicy:
        return selection_lib.resolve_policy(
            self.policy, constants=self.constants, case=self.case,
            k_b=self.k_b, select_prob=self.select_prob)

    def resolved_channel_model(self, u: int) -> chan.ChannelModel:
        return chan.resolve_model(self.channel_model, u, self.channel)


class RoundState(NamedTuple):
    """Scan carry: everything Algorithm 1 threads between rounds."""
    flat: jax.Array      # (D,) current global parameters, flattened
    w_prev2: jax.Array   # (D,) previous round's parameters (for eta)
    delta: jax.Array     # Delta_{t-1} (Lemma-1 recursion), f32 scalar
    t: jax.Array         # round index, i32 scalar
    key: jax.Array       # PRNG key for this and later rounds
    chan: Any = ()       # ChannelModel carry (e.g. Gauss-Markov state)


class RoundStats(NamedTuple):
    selected: jax.Array  # mean over entries of sum_i beta_i
    b_mean: jax.Array    # mean over entries of b
    a_t: jax.Array       # realized Theorem-1 contraction A_t (eq. 14)
    b_t: jax.Array       # realized Theorem-1 additive gap B_t (eq. 15)
    eta: jax.Array       # mean gradient-proxy magnitude (footnote 4)
    snr: jax.Array       # effective post-aggregation SNR (0 = noiseless)


def build_ota_stage(cfg: FLConfig, k_i: jax.Array, D: int,
                    model: Optional[chan.ChannelModel] = None,
                    wmask: Optional[jax.Array] = None
                    ) -> Callable[..., Any]:
    """Channel draw + policy + aggregation + convergence bookkeeping.

    Returns ``stage(W, w_prev, w_prev2, delta_prev, chan_carry, kchan,
    kpol, t) -> (new_flat, delta, chan_carry, selected, b_mean, a_t,
    b_t, eta_mean, snr)`` — the post-local-update part of a round,
    shared by all policies and both backends (and benchmarked
    head-to-head in ``benchmarks/kernels_micro.py``).  ``a_t`` / ``b_t``
    are the REALIZED Lemma-1 terms of this round (from the beta-free
    reductions), so callers can accumulate the paper's convergence bound
    along any trajectory without re-deriving beta.  ``eta_mean`` is the
    mean of the footnote-4 gradient proxy driving the power search, and
    ``snr`` the effective post-aggregation SNR — mean signal power over
    the per-entry descaled noise power ``sigma2 / (den_ki * b)^2`` —
    both per-round telemetry for the observability layer (the error-free
    oracle reports 0 for each).

    The function resolves the policy and channel model ONCE at build time
    (callers that also need the model, e.g. for carry init, may pass a
    pre-resolved instance via ``model``) and contains no per-name
    branches: exactness and kernel fusion are capabilities the policy
    object advertises (``policy.exact``, ``policy.fused_stage(backend)``),
    so new scenarios plug in without editing this module.

    ``wmask`` (optional (U,) of 1.0/0.0, possibly traced) marks which
    workers are REAL: ragged sweep cohorts pad the worker axis to a
    cohort-wide U_max, and the stage silences padded workers by zeroing
    their k_i / k_eff / p_max (they then transmit nothing, select
    nothing, and drop out of every denominator and statistic).  None —
    the default everywhere outside the sweep engine — keeps the compiled
    graph identical to the unpadded engine.
    """
    U = k_i.shape[0]
    backend = cfg.resolved_backend()
    policy = cfg.resolved_policy()
    if model is None:
        model = cfg.resolved_channel_model(U)
    k_eff = (jnp.full((U,), float(cfg.k_b), jnp.float32)
             if cfg.k_b is not None else k_i)
    p_max = jnp.full((U,), cfg.channel.p_max, jnp.float32)
    if wmask is not None:
        k_i = k_i * wmask
        k_eff = k_eff * wmask
        p_max = p_max * wmask
    c = cfg.constants

    if getattr(policy, "exact", False):
        # Error-free oracle (e.g. 'perfect'): exact weighted FedAvg, no
        # channel, no noise, Delta recursion unchanged.  Masked workers
        # have k_i = 0, so they drop out of the weighted average and the
        # selected count reports only real workers.
        n_real = jnp.float32(U) if wmask is None else jnp.sum(wmask)

        def exact_stage(W, w_prev, w_prev2, delta_prev, chan_carry,
                        kchan, kpol, t):
            del w_prev, w_prev2, kchan, kpol, t
            # error-free aggregation realizes the ideal Lemma-2 rate:
            # A_t = 1 - mu/L (no selection penalty), B_t = 0 (no noise)
            return (agg.fedavg(W, k_i), delta_prev, chan_carry,
                    n_real, jnp.float32(0.0),
                    jnp.float32(1.0 - c.mu / c.L), jnp.float32(0.0),
                    jnp.float32(0.0), jnp.float32(0.0))
        return exact_stage

    fused = None
    if hasattr(policy, "fused_stage"):
        fused = policy.fused_stage(backend.value)

    if backend is Backend.PALLAS:
        def aggregate(W, h_true, h_est, beta, b, noise):
            return kops.ota_aggregate(W, h_true[:, None], beta, b, noise,
                                      k_eff, p_max,
                                      h_est=h_est[:, None])
    else:
        def aggregate(W, h_true, h_est, beta, b, noise):
            w_hat, _ = agg.ota_aggregate(W, h_true[:, None], beta, b,
                                         k_eff, p_max, noise,
                                         h_est=h_est[:, None])
            return w_hat

    def stage(W, w_prev, w_prev2, delta_prev, chan_carry, kchan, kpol, t):
        kg, kn = chan.round_keys(kchan, t)
        chan_carry, h_true = model.step(chan_carry, kg, t)
        h_est = model.estimate(h_true, chan.estimate_key(kg))
        noise = chan.sample_noise(kn, (D,), cfg.channel)
        eta = jnp.abs(w_prev - w_prev2) + 1e-8   # paper footnote 4
        numer = case_numerator(cfg.case, k_i, c, delta_prev, cfg.k_b)
        ctx = selection_lib.PolicyContext(
            h_est=h_est, w_prev_abs=jnp.abs(w_prev), eta=eta,
            k_eff=k_eff, k_i=k_i, p_max=p_max, numer=numer,
            delta_prev=delta_prev, t=t, wmask=wmask)

        # named scopes label the ops in a profiler trace; metadata only
        if fused is not None:
            # one kernel searches and transmits: its ops carry both scopes
            with jax.named_scope("inflota.search"), \
                    jax.named_scope("ota.transmit"):
                w_hat, b, den_keff, den_ki, sel = fused(W, h_true, noise,
                                                        ctx)
        else:
            with jax.named_scope("inflota.search"):
                dec = policy.decide(kpol, ctx)
            with jax.named_scope("ota.transmit"):
                w_hat = aggregate(W, h_true, h_est, dec.beta, dec.b, noise)
            b = dec.b
            den_keff, den_ki = dec.reductions
            sel = dec.sel

        # entries with no selected worker keep the previous value
        new_flat = jnp.where(den_keff > _EPS, w_hat, w_prev)
        a_t = conv.A_t_from_den(den_ki, k_i, c)
        b_t = conv.B_t_from_den(den_ki, b, k_i, c)
        delta = b_t + a_t * delta_prev
        # effective post-aggregation SNR: per-entry descaled noise has
        # variance sigma2 / (den_ki * b)^2 (the B_t noise norm), so the
        # realized signal-to-noise at the PS is mean signal power over
        # mean noise power — 0-guarded for all-silent rounds
        # pinned_mean + reciprocal-multiply keep this scalar byte-stable
        # across compiled programs (batch padding, SPMD partitioning):
        # the reduce order is pinned and the explicit reciprocal avoids
        # XLA's approximate fused-divide lowering in vectorized contexts
        noise_pow = c.sigma2 * pinned_mean(
            1.0 / jnp.maximum(den_ki * b, _EPS) ** 2)
        snr = pinned_mean(new_flat ** 2) * (
            1.0 / jnp.maximum(noise_pow, _EPS))
        return (new_flat, delta, chan_carry, jnp.mean(sel), jnp.mean(b),
                a_t, b_t, jnp.mean(eta), snr)

    return stage


class Engine(NamedTuple):
    step: Callable[[RoundState, Any], tuple]
    unravel: Callable[[jax.Array], Any]
    D: int
    init: Callable[[jax.Array, jax.Array], RoundState]


def build_engine(task, X, Y, mask, k_i, cfg: FLConfig, params0,
                 wmask: Optional[jax.Array] = None) -> Engine:
    """Assemble the full jit/scan-compatible round step.

    Args:
      task:    TaskModel (init/loss/metrics pure functions).
      X, Y:    (U, K_max, ...) worker datasets padded to a uniform K_max.
      mask:    (U, K_max) 1.0 for real samples, 0.0 for padding.
      k_i:     (U,) true per-worker sample counts.
      params0: parameter pytree template (defines flatten/unflatten).
      wmask:   optional (U,) real-worker mask for ragged cohorts (padded
               workers carry all-zero sample masks and k_i = 0); None
               keeps the unpadded graph.

    ``cfg.worker_sharding`` routes to the worker-sharded twin engine
    (``fl.worker_shard.build_sharded_engine``), which streams the round
    in (U/S, D) blocks and never materializes the (U, D) update matrix.
    """
    if cfg.worker_sharding is not None:
        from repro.fl import worker_shard
        return worker_shard.build_sharded_engine(
            task, X, Y, mask, k_i, cfg, params0, wmask=wmask)
    flat0, unravel = ravel_pytree(params0)
    D = flat0.shape[0]
    U = k_i.shape[0]
    if cfg.k_b is not None and not isinstance(mask, jax.core.Tracer):
        # padded no-replacement sampling cannot raise per worker inside the
        # traced step (the old per-worker path did); validate up front so a
        # too-large minibatch fails loudly instead of drawing zero-padding.
        # Skipped when ``mask`` is itself traced (the sweep engine vmaps
        # whole runs over an experiment axis) — cohort builders validate
        # against the concrete mask before batching.
        # numpy, not jnp: under a jit/vmap trace (the sweep engine) jnp
        # ops are staged even on concrete operands and can't concretize
        min_k = int(np.min(np.sum(np.asarray(mask), axis=1)))
        if cfg.k_b > min_k:
            raise ValueError(
                f"k_b={cfg.k_b} exceeds the smallest worker's sample "
                f"count ({min_k}); minibatch sampling would draw padding")
    # resolve the channel model ONCE and share the instance between the
    # stage (step) and the carry initializer (init)
    model = cfg.resolved_channel_model(U)
    ota_stage = build_ota_stage(cfg, k_i, D, model=model, wmask=wmask)

    def local_stage(flat, klocal):
        """All workers' updates in one vmap-batched dispatch -> (U, D).

        Per-worker keys come from ``chan.worker_keys`` (fold_in by worker
        index), which is restriction-stable under worker padding — the
        same property the channel models guarantee — so ragged cohorts
        reproduce each cell's standalone key streams exactly.
        """
        params = unravel(flat)
        keys = chan.worker_keys(klocal, U)
        return jax.vmap(
            lambda x, y, m, k: ravel_pytree(local_update_masked(
                task, params, x, y, m, cfg.lr, key=k, k_b=cfg.k_b))[0]
        )(X, Y, mask, keys)

    def step(state: RoundState, _=None):
        key_next, klocal, kchan, kpol = jax.random.split(state.key, 4)
        with jax.named_scope("fl.local_update"):
            W = local_stage(state.flat, klocal)
        (new_flat, delta, chan_carry, sel, b_mean, a_t, b_t, eta_mean,
         snr) = ota_stage(
            W, state.flat, state.w_prev2, state.delta, state.chan,
            kchan, kpol, state.t)
        new_state = RoundState(flat=new_flat, w_prev2=state.flat,
                               delta=delta, t=state.t + 1, key=key_next,
                               chan=chan_carry)
        return new_state, RoundStats(selected=sel, b_mean=b_mean,
                                     a_t=a_t, b_t=b_t, eta=eta_mean,
                                     snr=snr)

    def init(flat: jax.Array, key: jax.Array) -> RoundState:
        # The model's init key is DERIVED (not split off) so memoryless
        # scenarios reproduce the legacy per-round key streams exactly.
        carry = model.init_state(jax.random.fold_in(key, 0x636861))
        return init_state(flat, key, chan_carry=carry)

    return Engine(step=step, unravel=unravel, D=D, init=init)


def init_state(flat: jax.Array, key: jax.Array,
               chan_carry: Any = ()) -> RoundState:
    # delta follows the parameter dtype so the scan carry stays uniform
    # whether or not x64 is enabled
    return RoundState(flat=flat, w_prev2=flat,
                      delta=jnp.zeros((), flat.dtype),
                      t=jnp.int32(0), key=key, chan=chan_carry)
