"""Worker-sharded OTA rounds: the sharded == unsharded harness (ISSUE 9).

Exactness tiers, pinned with the same discipline as ``test_ragged``:

  * ``worker_sharding = 1`` (jnp backend) is BIT-EXACT against the dense
    engine for every policy — the single shard block reproduces the
    dense op order end to end;
  * any shard count S: the distributed Theorem-4 search returns the
    IDENTICAL (b, beta, r) as ``core/inflota.solve`` (the per-shard
    sorted-prefix reduction is exact: feasibility thresholds are
    compared with the same literal tolerance and the den sums are
    integer-valued f32), and a round's decision statistics are bit-equal
    to the dense engine's when evaluated from the same state;
  * S > 1 trajectories match dense within f32 reassociation tolerance
    (only the received superposition re-groups; same RAGGED_RTOL tier as
    the ragged cohorts);
  * sharded-pallas (``ota_shard_tx``: beta rebuilt in VMEM, only (D,)
    partials leave the kernel) is bit-exact against sharded-jnp;
  * a U = 10^5 round never materializes any (U, D) intermediate —
    asserted on the jaxpr, not trusted from the code shape;
  * per-worker randomness is restriction-stable across repartitions, so
    every shard count consumes the same per-worker streams;
  * post-aggregation SNR grows at least linearly in U under ExpIID (the
    blessing-of-scaling trend ``benchmarks/fig_scaling_u.py`` measures
    at U = 10^4..10^6).

Randomized-instance coverage lives here (seeded, deterministic, runs in
tier-1); the hypothesis ``@given`` property suite with generated shapes
is ``test_worker_sharded_props.py`` (skipped when hypothesis is absent,
like the other property modules).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import channel as chan
from repro.core import inflota
from repro.core.convergence import LearningConstants
from repro.data.tasks import build_task_data
from repro.fl import worker_shard
from repro.fl.engine import FLConfig, build_engine
from repro.fl.models import linreg_model
from repro.fl.trainer import pad_workers

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _float32_mode():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


RAGGED_RTOL = 2e-6      # cross-program f32 reassociation (test_ragged tier)


def _run(cfg, U=12, rounds=3, k_bar=10, data_seed=3, seed=0, mesh=None):
    """Engine trajectory: final flat params + per-round stats stacks."""
    task, workers, _ = build_task_data("linreg", U=U, k_bar=k_bar,
                                       data_seed=data_seed)
    X, Y, mask, k_i = pad_workers(workers)
    params0 = task.init(jax.random.PRNGKey(7))
    if mesh is not None:
        eng = worker_shard.build_sharded_engine(
            task, X, Y, mask, k_i, cfg, params0, mesh=mesh)
    else:
        eng = build_engine(task, X, Y, mask, k_i, cfg, params0)
    flat0, _ = ravel_pytree(params0)
    st = eng.init(flat0, jax.random.PRNGKey(seed))
    step = jax.jit(eng.step)
    stats = []
    for _ in range(rounds):
        st, s = step(st)
        stats.append(s)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *stats)
    return np.asarray(st.flat), stacked


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- S = 1 bit-exactness

@pytest.mark.parametrize("policy", ["inflota", "random", "all", "perfect"])
@pytest.mark.parametrize("k_b", [None, 5])
def test_s1_bitexact_vs_dense(policy, k_b):
    """One shard block = the dense engine, bit for bit: flat params AND
    every per-round statistic, for every policy and GD/SGD."""
    base = dict(rounds=3, lr=0.05, policy=policy, k_b=k_b,
                constants=LearningConstants(sigma2=1e-4))
    f_dense, s_dense = _run(FLConfig(**base))
    f_s1, s_s1 = _run(FLConfig(**base, worker_sharding=1))
    np.testing.assert_array_equal(f_dense, f_s1)
    _assert_trees_equal(s_dense, s_s1)


# --------------------------------------- S > 1: tolerance + exact decisions

@pytest.mark.parametrize("policy", ["inflota", "random", "all"])
@pytest.mark.parametrize("n_shards", [2, 3, 4, 6])
def test_sharded_matches_dense_within_tolerance(policy, n_shards):
    """Sharded trajectories track dense within the reassociation tier;
    the FIRST round (identical input state on both paths) has bit-equal
    decision statistics — only the y superposition re-groups."""
    base = dict(rounds=3, lr=0.05, policy=policy,
                constants=LearningConstants(sigma2=1e-4))
    f_dense, s_dense = _run(FLConfig(**base))
    f_shard, s_shard = _run(FLConfig(**base, worker_sharding=n_shards))
    np.testing.assert_allclose(f_shard, f_dense, rtol=RAGGED_RTOL,
                               atol=1e-7)
    # round-0 decisions: selection count, power scaling, Lemma-1 terms
    for name in ("selected", "b_mean", "a_t", "b_t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_dense, name))[0],
            np.asarray(getattr(s_shard, name))[0])


def test_padding_shard_counts_match():
    """S that does not divide U pads with inert workers; the padded run
    stays within tolerance of dense (restriction-stable streams + padded
    workers transmit nothing and join no denominator)."""
    base = dict(rounds=3, lr=0.05, policy="inflota",
                constants=LearningConstants(sigma2=1e-4))
    f_dense, _ = _run(FLConfig(**base))           # U = 12
    for s in (5, 7):                              # pads 12 -> 15 / 14
        f_pad, _ = _run(FLConfig(**base, worker_sharding=s))
        np.testing.assert_allclose(f_pad, f_dense, rtol=RAGGED_RTOL,
                                   atol=1e-7)


def test_padding_refused_for_non_restriction_stable_channel():
    """Pathloss couples workers through ensemble normalization — padding
    would shift every draw, so a non-divisor S must fail loudly."""
    base = dict(rounds=2, lr=0.05, policy="inflota",
                channel_model="pathloss",
                constants=LearningConstants(sigma2=1e-4))
    _run(FLConfig(**base, worker_sharding=3))     # divisor of 12: fine
    with pytest.raises(ValueError, match="restriction-stable"):
        _run(FLConfig(**base, worker_sharding=5))


def test_entry_level_non_inflota_policy_rejected():
    """Worker-sharded rounds support entry-level beta only through the
    distributed inflota path; a custom dense-beta policy fails loudly at
    trace time instead of silently mis-slicing."""
    import dataclasses

    from repro.core import selection as selection_lib

    @dataclasses.dataclass(frozen=True)
    class DenseBeta(selection_lib.RoundPolicyBase):
        def decide(self, key, ctx):
            D = ctx.w_prev_abs.shape[0]
            U = ctx.h_est.shape[0]
            return selection_lib.make_decision(
                jnp.ones((D,)), jnp.ones((U, D), jnp.float32),
                ctx.k_eff, ctx.k_i, wmask=ctx.wmask)

    base = dict(rounds=1, lr=0.05, policy=DenseBeta(),
                constants=LearningConstants(sigma2=1e-4))
    with pytest.raises(ValueError, match="entry-level selection"):
        _run(FLConfig(**base, worker_sharding=2), rounds=1)


# ------------------------------------- distributed Theorem-4 search: exact

def test_distributed_inflota_matches_solve_exactly():
    """solve_sharded == solve (b, beta, r all bit-equal) on randomized
    instances spanning shard counts, K_b, and masked (inert) workers —
    the ISSUE-9 acceptance bar for the distributed search."""
    rng = np.random.default_rng(0)
    c = LearningConstants(sigma2=1e-4)
    for trial in range(20):
        n_shards = int(rng.integers(1, 9))
        u_b = int(rng.integers(1, 7))
        U = n_shards * u_b
        D = int(rng.integers(1, 9))
        h = jnp.asarray(rng.exponential(size=(U,)).astype(np.float32))
        k_i = jnp.asarray(
            rng.integers(1, 40, size=(U,)).astype(np.float32))
        if trial % 3 == 0 and U > 1:      # inert (masked) workers
            drop = rng.integers(0, U, size=max(U // 4, 1))
            k_i = k_i.at[drop].set(0.0)
        p_max = jnp.where(k_i > 0, 10.0, 0.0)
        w_abs = jnp.asarray(
            rng.uniform(0.01, 2.0, size=(D,)).astype(np.float32))
        eta = jnp.asarray(
            rng.uniform(1e-4, 0.5, size=(D,)).astype(np.float32))
        K_b = float(rng.integers(1, 10)) if trial % 2 else None
        delta_prev = float(rng.uniform(0, 2))
        ref = inflota.solve(h[:, None], k_i, w_abs, eta, p_max, c,
                            delta_prev=delta_prev, K_b=K_b)
        got = inflota.solve_sharded(h, k_i, w_abs, eta, p_max, c,
                                    n_shards=n_shards,
                                    delta_prev=delta_prev, K_b=K_b)
        np.testing.assert_array_equal(np.asarray(ref.b), np.asarray(got.b))
        np.testing.assert_array_equal(np.asarray(ref.r), np.asarray(got.r))
        np.testing.assert_array_equal(np.asarray(ref.beta),
                                      np.asarray(got.beta))


def test_sharded_rank1_winner_consistency():
    """The winning candidate index is globally consistent: b equals the
    winner's cw times the s statistic, and the winner block/offset match
    the two-level argmin."""
    rng = np.random.default_rng(1)
    c = LearningConstants(sigma2=1e-4)
    U, S, D = 24, 4, 6
    h = jnp.asarray(rng.exponential(size=(U,)).astype(np.float32))
    k_i = jnp.asarray(rng.integers(1, 30, size=(U,)).astype(np.float32))
    w_abs = jnp.asarray(rng.uniform(0.1, 1, size=(D,)).astype(np.float32))
    eta = jnp.asarray(rng.uniform(1e-3, 0.2, size=(D,)).astype(np.float32))
    sol = inflota.solve_rank1_sharded(h, k_i, w_abs, eta, 10.0, c,
                                      n_shards=S)
    cw_flat = np.asarray(sol.cw).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(sol.b),
        cw_flat[np.asarray(sol.kstar)] * np.asarray(sol.s))


# ------------------------------------------------- restriction stability

def test_worker_streams_restriction_stable_across_repartitions():
    """Every repartition (and the inert padding) consumes the same
    per-worker key streams: fold_in by GLOBAL worker index."""
    key = jax.random.PRNGKey(11)
    full = chan.worker_keys(key, 15)
    np.testing.assert_array_equal(np.asarray(chan.worker_keys(key, 12)),
                                  np.asarray(full[:12]))


def test_repartitions_agree_within_tolerance():
    """S = 2 / 3 / 4 / 6 runs of the same config agree pairwise at the
    reassociation tier — the shard count only re-groups the y sum."""
    base = dict(rounds=3, lr=0.05, policy="inflota",
                constants=LearningConstants(sigma2=1e-4))
    flats = [_run(FLConfig(**base, worker_sharding=s))[0]
             for s in (2, 3, 4, 6)]
    for f in flats[1:]:
        np.testing.assert_allclose(f, flats[0], rtol=RAGGED_RTOL,
                                   atol=1e-7)


# ------------------------------------------------------ pallas tile kernel

@pytest.mark.parametrize("k_b", [None, 5])
def test_pallas_sharded_bitexact_vs_jnp_sharded(k_b):
    """``ota_shard_tx`` mirrors the jnp block ops literally (beta
    membership, Algorithm-1 clipping, partial reductions) — sharded
    pallas == sharded jnp bit-for-bit, at every shard count."""
    for s in (1, 3):
        base = dict(rounds=3, lr=0.05, policy="inflota", k_b=k_b,
                    constants=LearningConstants(sigma2=1e-4),
                    worker_sharding=s)
        f_jnp, s_jnp = _run(FLConfig(**base, backend="jnp"))
        f_pal, s_pal = _run(FLConfig(**base, backend="pallas"))
        np.testing.assert_array_equal(f_jnp, f_pal)
        _assert_trees_equal(s_jnp, s_pal)


# ----------------------------------------- no (U, D) materialization @ 1e5

def test_u1e5_round_never_materializes_global_ud():
    """Trace a U = 10^5 sharded round and walk the jaxpr (including every
    sub-jaxpr): no intermediate may reach U * D elements.  The biggest
    legitimate arrays are the (U, K) worker data and (U,)-sized channel
    vectors; local updates / beta tiles exist only at (U/S, D)."""
    U, K, S = 100_000, 2, 100
    task = linreg_model()
    X = jnp.zeros((U, K), jnp.float32)
    Y = jnp.zeros((U, K), jnp.float32)
    mask = jnp.ones((U, K), jnp.float32)
    k_i = jnp.full((U,), float(K), jnp.float32)
    params0 = task.init(jax.random.PRNGKey(0))
    cfg = FLConfig(rounds=1, lr=0.05, policy="inflota", worker_sharding=S,
                   constants=LearningConstants(sigma2=1e-4))
    eng = build_engine(task, X, Y, mask, k_i, cfg, params0)
    flat0, _ = ravel_pytree(params0)
    D = flat0.shape[0]
    st = eng.init(flat0, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(eng.step)(st)

    limit = U * D
    offenders = []

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    if int(np.prod(aval.shape, dtype=np.int64)) >= limit:
                        offenders.append((eqn.primitive.name, aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert not offenders, f"(U, D)-sized intermediates traced: {offenders}"

    # and no (S, D) stack either: at one worker a block of a wide model
    # (the 784-64-10 MLP, D = 50,890, S = U = 4), every value that
    # crosses blocks is a running (D,) value, under both backends
    from repro.fl.models import mlp_model
    U, K, S = 4, 10, 4
    task = mlp_model()
    X = jnp.zeros((U, K, 784), jnp.float32)
    Y = jnp.zeros((U, K), jnp.int32)
    mask = jnp.ones((U, K), jnp.float32)
    k_i = jnp.full((U,), float(K), jnp.float32)
    params0 = task.init(jax.random.PRNGKey(0))
    flat0, _ = ravel_pytree(params0)
    limit = S * flat0.shape[0]
    for backend in ("jnp", "pallas"):
        cfg = FLConfig(rounds=1, lr=0.05, policy="inflota", k_b=2,
                       worker_sharding=S, backend=backend,
                       constants=LearningConstants(sigma2=1e-4))
        eng = build_engine(task, X, Y, mask, k_i, cfg, params0)
        st = eng.init(flat0, jax.random.PRNGKey(0))
        offenders = []
        walk(jax.make_jaxpr(eng.step)(st).jaxpr)
        assert not offenders, (
            f"(S, D)-sized intermediates traced ({backend}): {offenders}")


def test_running_envelope_matches_stacked_reduction():
    """The engine's running first-min over blocks
    (``inflota.envelope_step``) gives bit for bit the b, r and kstar of
    the stacked ``reduce_envelopes``, ties included (duplicated
    candidates in different blocks, equal curves across entries)."""
    rng = np.random.default_rng(11)
    S, u_b, D = 5, 4, 257
    c = LearningConstants(sigma2=1e-3)
    cw = rng.uniform(0.1, 2.0, (S, u_b)).astype(np.float32)
    cw[3, 1] = cw[1, 2]                  # the same candidate twice
    cw[4] = cw[0]                        # a whole block repeated
    cw = jnp.asarray(cw)
    k_den = jnp.asarray(rng.integers(5, 20, (S, u_b)), jnp.float32)
    s = jnp.asarray(rng.uniform(0.2, 3.0, D), jnp.float32)
    s = s.at[:64].set(s[0])              # equal curves across entries
    numer = jnp.float32(37.0)
    th, cs = jax.vmap(inflota.block_summary)(cw, k_den)
    dens = [inflota.block_den(cw[j], th, cs) for j in range(S)]
    stacked = [inflota.block_envelope(cw[j], dens[j], s, c, numer)
               for j in range(S)]
    b_ref, r_ref, k_ref = inflota.reduce_envelopes(
        *[jnp.stack(v) for v in zip(*stacked)], s, u_b)
    carry = (jnp.full((D,), jnp.inf, jnp.float32),
             jnp.zeros((D,), jnp.int32))
    for j in range(S):
        carry = inflota.envelope_step(carry, cw[j], dens[j], s, c, numer,
                                      j * u_b)
    r, kstar = carry
    np.testing.assert_array_equal(np.asarray(kstar), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
    np.testing.assert_array_equal(
        np.asarray(jnp.take(cw.reshape(-1), kstar) * s), np.asarray(b_ref))


# --------------------------------------------------- blessing of scaling

def test_snr_grows_at_least_linearly_in_u():
    """ExpIID + random policy: the realized post-aggregation SNR
    (``RoundStats.snr``) grows at least linearly in U.  The random
    policy's b draw and per-worker Bernoulli selection are
    restriction-stable, so growing U keeps b and every existing worker's
    selection bit fixed while the descale denominator gains ~U/2 new
    selected workers — descaled noise power drops ~U^-2 against a
    U-independent signal.  (INFLOTA is deliberately NOT the policy here:
    its Theorem-4 search re-optimizes b downward as the candidate pool
    grows, so its realized SNR need not be monotone in U — the
    blessing-of-scaling figure measures, rather than assumes, its
    trend.)  Pins the noise-washout mechanism on tiny U."""
    us = (8, 32, 128)
    snrs = []
    for u in us:
        cfg = FLConfig(rounds=3, lr=0.05, policy="random",
                       constants=LearningConstants(sigma2=1e-4))
        _, stats = _run(cfg, U=u, rounds=3)
        snrs.append(float(np.asarray(stats.snr)[-1]))
    assert snrs[0] > 0
    assert snrs == sorted(snrs), f"SNR not monotone in U: {snrs}"
    slopes = np.diff(np.log(snrs)) / np.diff(np.log(us))
    assert np.all(slopes > 1.0), \
        f"SNR growth sub-linear in U: slopes {slopes} for snrs {snrs}"


# --------------------------------------------------------- sweep integration

def test_sweep_u_shards_axis_and_s1_bitexact():
    """U_shards is a cohort-static cell axis: the grid splits per shard
    count (never ragged-merged), scalar axes still vectorize within each
    cohort, and the S = 1 cells are bit-identical to the dense cells."""
    from repro.sweep import SweepSpec, run_spec
    from repro.sweep.grid import cells, cohorts

    spec = SweepSpec(axes={"U_shards": (None, 1, 3),
                           "sigma2": (1e-4, 1e-2)},
                     base={"U": 12, "k_bar": 8, "rounds": 3})
    cos = cohorts(cells(spec))
    got = sorted(((c.static["U_shards"], len(c)) for c in cos),
                 key=lambda t: (t[0] is not None, t[0] or 0))
    assert got == [(None, 2), (1, 2), (3, 2)]
    by = {(r["cell"]["U_shards"], r["cell"]["sigma2"]):
          np.asarray(r["flat"]) for r in run_spec(spec)}
    for s2 in (1e-4, 1e-2):
        np.testing.assert_array_equal(by[(1, s2)], by[(None, s2)])
        np.testing.assert_allclose(by[(3, s2)], by[(None, s2)],
                                   rtol=RAGGED_RTOL, atol=1e-7)


# ----------------------------------------------------- multi-device checks

_SUBPROCESS_ENV = dict(
    os.environ,
    XLA_FLAGS="--xla_force_host_platform_device_count=4",
    JAX_PLATFORMS="cpu",
    PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] + sys.path))


def test_multidevice_sharded_sweep_store_byte_identical():
    """4 forced host devices: an experiment-mesh-sharded sweep of a
    ``U_shards`` grid writes a store byte-identical (excluding meta/) to
    the 1-device serial run — worker sharding always executes in logical
    mode, so values depend on S, never on the device count."""
    prog = r"""
import filecmp, os, tempfile
import numpy as np
import jax
jax.config.update("jax_platform_name", "cpu")
assert len(jax.devices()) == 4, jax.devices()
from repro.sweep import SweepSpec, SweepStore, run_spec
from repro.sweep import shard as shard_lib

spec = SweepSpec(axes={"U_shards": (1, 4), "seed": (0, 1, 2)},
                 base={"U": 8, "k_bar": 8, "rounds": 3})
tmp = tempfile.mkdtemp()
a, b = os.path.join(tmp, "serial"), os.path.join(tmp, "sharded")
run_spec(spec, store=SweepStore(a))
run_spec(spec, store=SweepStore(b), mesh=shard_lib.sweep_mesh(), jobs=2)

def files(root):
    out = {}
    for dirpath, _, names in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        if rel.split(os.sep)[0] == "meta":
            continue
        for n in names:
            out[os.path.normpath(os.path.join(rel, n))] = \
                os.path.join(dirpath, n)
    return out

fa, fb = files(a), files(b)
assert set(fa) == set(fb), (sorted(fa), sorted(fb))
assert fa, "store is empty"
for rel in sorted(fa):
    assert filecmp.cmp(fa[rel], fb[rel], shallow=False), rel
print("STORE-IDENTICAL", len(fa))
"""
    out = subprocess.run([sys.executable, "-c", prog],
                         env=_SUBPROCESS_ENV, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "STORE-IDENTICAL" in out.stdout


def test_multidevice_worker_mesh_matches_logical():
    """4 forced host devices: shard_map execution over the 'data' worker
    axis tracks logical-mode execution within reassociation tolerance,
    with a bit-equal first-round Theorem-4 decision."""
    prog = r"""
import numpy as np
import jax
jax.config.update("jax_platform_name", "cpu")
assert len(jax.devices()) == 4, jax.devices()
from jax.flatten_util import ravel_pytree
from repro.core.convergence import LearningConstants
from repro.data.tasks import build_task_data
from repro.fl import worker_shard
from repro.fl.engine import FLConfig
from repro.fl.trainer import pad_workers

task, workers, _ = build_task_data("linreg", U=16, k_bar=8, data_seed=3)
X, Y, mask, k_i = pad_workers(workers)
params0 = task.init(jax.random.PRNGKey(7))
mesh = worker_shard.worker_mesh()
assert mesh is not None and dict(mesh.shape)["data"] == 4

for policy in ("inflota", "random", "all", "perfect"):
    cfg = FLConfig(rounds=3, lr=0.05, policy=policy, worker_sharding=8,
                   constants=LearningConstants(sigma2=1e-4))
    outs = []
    for m in (None, mesh):
        eng = worker_shard.build_sharded_engine(
            task, X, Y, mask, k_i, cfg, params0, mesh=m)
        flat0, _ = ravel_pytree(params0)
        st = eng.init(flat0, jax.random.PRNGKey(0))
        step = jax.jit(eng.step)
        stats = []
        for _ in range(3):
            st, s = step(st)
            stats.append(s)
        outs.append((np.asarray(st.flat), stats))
    (fl, sl), (fm, sm) = outs
    np.testing.assert_allclose(fm, fl, rtol=2e-6, atol=1e-7)
    for name in ("selected", "b_mean", "a_t", "b_t"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sl[0], name)),
            np.asarray(getattr(sm[0], name)))
print("WORKER-MESH-OK")
"""
    out = subprocess.run([sys.executable, "-c", prog],
                         env=_SUBPROCESS_ENV, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "WORKER-MESH-OK" in out.stdout
