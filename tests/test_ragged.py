"""Single-compile sweep cohorts: ragged worker padding + traced scalars.

Four families of checks (ISSUE 4):

  * partition semantics — a U x eps x sigma2 grid that previously split
    into one cohort per (U, eps) combination is ONE cohort per backend;
  * exactness tiers, stated precisely:
      - same-shape cohorts with traced eps / rho / sigma2 operands are
        BIT-EXACT against sequential ``FLTrainer`` runs (the operand
        arithmetic is pinned to the array dtype on both paths);
      - the ragged MASKING itself is bit-exact at op level: an eagerly
        evaluated padded+masked round reproduces the unpadded round
        bit-for-bit (restriction-stable worker keys + exact-zero padded
        contributions);
      - whole ragged cohorts match sequential runs to float32
        reassociation tolerance (~1e-6 relative): XLA regroups SIMD
        reductions when the worker-axis extent changes, which is the one
        thing zero-padding cannot hold fixed across compiled programs;
  * ragged edge shapes — a U=1 cohort member and a cell whose mask pads
    out most of the cohort's workers;
  * guard rails — mixed None/number scalar axes and instance channels in
    ragged cohorts fail loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import channel as chan
from repro.core.channel import (ChannelConfig, ExpIID, GaussMarkovFading,
                                ImperfectCSI, make_channel)
from repro.core.convergence import LearningConstants
from repro.core.objectives import Case
from repro.data.tasks import build_task_data
from repro.fl.engine import build_ota_stage
from repro.fl.trainer import FLConfig, FLTrainer, pad_workers
from repro.sweep import SweepSpec, run_spec
from repro.sweep.grid import cells, cohorts, result_by

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _float32_mode():
    """The engine runs f32 in production; other test modules flip the
    global x64 switch at import, which changes the RNG streams and the
    traced-vs-concrete scalar promotion these exactness tests pin."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


K_BAR, ROUNDS = 10, 6
# float32 reassociation tolerance for cross-shape comparisons (see module
# docstring); same-shape comparisons assert exact equality instead
RAGGED_RTOL = 2e-6


def _sequential(cell, *, eval_data=None):
    """The standalone FLTrainer run a sweep cell must reproduce."""
    u = cell["U"]
    task, workers, test = build_task_data(
        cell["task"], U=u, k_bar=cell["k_bar"], data_seed=cell["data_seed"])
    model = cell["channel"]
    kw = {k: cell[k] for k in ("eps", "rho") if cell[k] is not None}
    chanc = ChannelConfig(sigma2=cell["sigma2"], p_max=cell["p_max"])
    if kw:
        model = chan.resolve_model(model, u, chanc, **kw)
    case = cell["case"] if isinstance(cell["case"], Case) \
        else Case(cell["case"])
    cfg = FLConfig(rounds=cell["rounds"], lr=cell["lr"],
                   policy=cell["policy"], case=case, k_b=cell["k_b"],
                   channel=chanc, channel_model=model,
                   constants=LearningConstants(sigma2=cell["sigma2"]),
                   backend=cell["backend"], scan=True)
    h = FLTrainer(task, workers, cfg).run(
        key=jax.random.PRNGKey(cell["seed"]),
        eval_data=test if eval_data is None else eval_data)
    return h, np.asarray(ravel_pytree(h["params"])[0])


# ----------------------------------------------------- partition semantics

def test_u_eps_sigma2_grid_is_one_cohort_per_backend():
    """The ISSUE-4 acceptance grid: 12 cells that the pre-ragged engine
    split into 6 cohorts (U x eps) compile ONCE, on both backends, and
    every cell matches its sequential twin within reassociation
    tolerance."""
    for backend in ("jnp", "pallas"):
        spec = SweepSpec(
            axes={"U": (3, 5, 8), "eps": (0.0, 0.1),
                  "sigma2": (1e-4, 1e-2)},
            base={"k_bar": K_BAR, "rounds": 5, "channel": "exp_iid_csi",
                  "backend": backend})
        cl = cells(spec)
        assert len(cl) == 12
        assert len(cohorts(cl, legacy=True)) == 6     # the old plan
        cos = cohorts(cl)
        assert len(cos) == 1 and cos[0].ragged        # the new plan
        results = run_spec(spec)
        for r in results:
            h, flat = _sequential(r["cell"])
            np.testing.assert_allclose(r["flat"], flat, rtol=RAGGED_RTOL,
                                       atol=1e-7)
            np.testing.assert_allclose(
                np.asarray(r["history"]["mse"]), np.asarray(h["mse"]),
                rtol=RAGGED_RTOL, atol=1e-8)


# ------------------------------------------------- exactness: same shapes

def test_traced_eps_bitexact_vs_static_path():
    """eps varies inside one cohort (traced operand, jnp.where rewrite of
    the eps == 0 Python branch) — every cell, INCLUDING eps = 0, is
    bit-exact against the old static-``ImperfectCSI`` sequential path."""
    spec = SweepSpec(axes={"eps": (0.0, 0.1, 0.3)},
                     base={"U": 6, "k_bar": K_BAR, "rounds": ROUNDS,
                           "channel": "exp_iid_csi", "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1
    for r in run_spec(spec):
        h, flat = _sequential(r["cell"])        # static float eps
        np.testing.assert_array_equal(r["flat"], flat)
        np.testing.assert_array_equal(np.asarray(r["history"]["mse"]),
                                      np.asarray(h["mse"]))


def test_traced_eps0_estimator_equals_static():
    """Estimator-level: a traced zero eps selects the perfect-CSI gains
    bit-for-bit (the jnp.where keeps h exactly)."""
    gains = jnp.asarray(np.random.default_rng(0).exponential(size=16),
                        jnp.float32)
    key = jax.random.PRNGKey(1)
    static = ImperfectCSI(ExpIID(u=16), eps=0.0).estimate(gains, key)

    def traced(eps):
        return ImperfectCSI(ExpIID(u=16), eps=eps).estimate(gains, key)

    out = jax.jit(traced)(jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(static))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gains))


def test_traced_rho_bitexact():
    """rho varies inside one Gauss-Markov cohort; previously each rho was
    its own compiled cohort (static dataclass field)."""
    spec = SweepSpec(axes={"rho": (0.5, 0.9)},
                     base={"U": 6, "k_bar": K_BAR, "rounds": ROUNDS,
                           "channel": "gauss_markov", "backend": "jnp"})
    cl = cells(spec)
    assert len(cohorts(cl)) == 1
    assert len(cohorts(cl, legacy=True)) == 2
    for r in run_spec(spec):
        h, flat = _sequential(r["cell"])        # static float rho
        np.testing.assert_array_equal(r["flat"], flat)


def test_traced_sigma2_pallas_single_cohort():
    """sigma2 swept through the PALLAS backend: the kernels take L /
    sigma2 as traced operands in a VMEM row, so the cohort no longer splits
    (nor falls back) — and matches sequential pallas runs."""
    spec = SweepSpec(axes={"sigma2": (1e-4, 1e-2)},
                     base={"U": 5, "k_bar": K_BAR, "rounds": 4,
                           "backend": "pallas"})
    assert len(cohorts(cells(spec))) == 1
    for r in run_spec(spec):
        h, flat = _sequential(r["cell"])
        np.testing.assert_array_equal(r["flat"], flat)


# ------------------------------------------------ exactness: masking level

def test_padded_masked_round_op_exact():
    """The load-bearing masking statement, free of XLA fusion effects:
    one eagerly evaluated OTA round on a (U + pad)-worker fleet with a
    worker mask reproduces the U-worker round BIT-exactly, because (a)
    per-worker randomness is restriction-stable and (b) padded workers
    contribute exact zeros to every reduction."""
    U, pad = 5, 3
    task, workers, _ = build_task_data("linreg", U=U, k_bar=K_BAR,
                                       data_seed=0)
    _, _, _, k_i = pad_workers(workers)
    D = 2
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
    w_prev = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    cfg = FLConfig(policy="inflota", channel=ChannelConfig(),
                   constants=LearningConstants(), backend="jnp")

    def one_round(u_total, Wm, k, wmask):
        stage = build_ota_stage(cfg, k, D, wmask=wmask)
        key = jax.random.PRNGKey(7)
        return stage(Wm, w_prev, w_prev, jnp.zeros(()), (),
                     key, jax.random.fold_in(key, 1), jnp.int32(0))

    plain = one_round(U, W, k_i, None)
    padded = one_round(
        U + pad,
        jnp.concatenate([W, jnp.tile(w_prev[None], (pad, 1))]),
        jnp.concatenate([k_i, jnp.zeros((pad,))]),
        jnp.asarray([1.0] * U + [0.0] * pad, jnp.float32))
    names = ("flat", "delta", "carry", "sel", "b", "a_t", "b_t",
             "eta", "snr")
    assert len(plain) == len(padded) == len(names)
    for a, b, name in zip(plain, padded, names):
        if name == "carry":
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ------------------------------------------------------- ragged edge cases

def test_u1_cohort_member():
    """A single-worker cell rides a ragged cohort: the Theorem-4 search
    degenerates to one candidate and the padded workers stay silent."""
    spec = SweepSpec(axes={"U": (1, 4)},
                     base={"k_bar": K_BAR, "rounds": ROUNDS,
                           "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1
    results = run_spec(spec)
    r1 = result_by(results, U=1)
    assert np.all(np.asarray(r1["history"]["selected"]) <= 1.0 + 1e-6)
    for r in results:
        h, flat = _sequential(r["cell"])
        np.testing.assert_allclose(r["flat"], flat, rtol=RAGGED_RTOL,
                                   atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(r["history"]["selected"]),
            np.asarray(h["selected"]), atol=1e-6)


def test_mostly_padded_cell():
    """A U=2 cell inside a U_max=16 cohort: 87% of its worker rows are
    padding, and none of them may select, transmit, or shift stats."""
    spec = SweepSpec(axes={"U": (2, 16)},
                     base={"k_bar": K_BAR, "rounds": ROUNDS,
                           "policy": "random", "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1
    results = run_spec(spec)
    small = result_by(results, U=2)
    assert np.all(np.asarray(small["history"]["selected"]) <= 2.0 + 1e-6)
    for r in results:
        h, flat = _sequential(r["cell"])
        np.testing.assert_allclose(r["flat"], flat, rtol=RAGGED_RTOL,
                                   atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(r["history"]["selected"]),
            np.asarray(h["selected"]), atol=1e-6)


def test_minibatch_cells_ride_ragged_cohorts():
    """SGD / k_b cells ragged-merge now (ISSUE 6): the per-sample
    ``fold_in`` minibatch sampler draws each sample's inclusion from a
    key that ignores the padded worker- and sample-axis extents, so a
    cell's batch picks are identical inside any cohort.  Every cell must
    match its standalone trainer run."""
    spec = SweepSpec(axes={"U": (4, 6)},
                     base={"k_bar": 12, "rounds": ROUNDS, "k_b": 3,
                           "case": "sgd", "backend": "jnp"})
    cos = cohorts(cells(spec))
    assert len(cos) == 1 and cos[0].ragged
    results = run_spec(spec)
    for r in results:
        h, flat = _sequential(r["cell"])
        np.testing.assert_allclose(r["flat"], flat, rtol=RAGGED_RTOL,
                                   atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(r["history"]["selected"]),
            np.asarray(h["selected"]), atol=1e-6)


def test_ragged_eval_uses_each_cells_test_split():
    """Per-cell test splits stack into a per-experiment eval operand; the
    mse history of every cell must match its standalone run, which
    evaluates against that cell's own split."""
    spec = SweepSpec(axes={"k_bar": (8, 20)},
                     base={"U": 5, "rounds": ROUNDS, "backend": "jnp"})
    assert len(cohorts(cells(spec))) == 1
    for r in run_spec(spec):
        h, _ = _sequential(r["cell"])
        np.testing.assert_allclose(
            np.asarray(r["history"]["mse"]), np.asarray(h["mse"]),
            rtol=RAGGED_RTOL, atol=1e-8)


# ------------------------------------------------------------- guard rails

def test_mixed_none_and_number_scalar_axis_rejected():
    spec = SweepSpec(axes={"eps": (None, 0.1)},
                     base={"U": 4, "k_bar": K_BAR, "rounds": 2,
                           "channel": "exp_iid_csi"})
    with pytest.raises(ValueError, match="mixes None"):
        run_spec(spec)


def test_eps_requires_compatible_channel():
    spec = SweepSpec(axes={"eps": (0.0, 0.1)},
                     base={"U": 4, "k_bar": K_BAR, "rounds": 2})
    with pytest.raises(ValueError, match="registry channel name"):
        run_spec(spec)


def test_instance_channel_cannot_span_ragged_u():
    spec = SweepSpec(axes={"U": (4, 6)},
                     base={"k_bar": K_BAR, "rounds": 2,
                           "channel": GaussMarkovFading(u=6)})
    # the instance is a static field, so each U still forms its own
    # cohort — but a hand-built ragged cohort must refuse it
    from repro.sweep.grid import Cohort, run_cohort
    cl = cells(spec)
    fake = Cohort(static={k: v for k, v in cl[0].items()
                          if k not in ("U", "seed")},
                  cells=cl, indices=list(range(len(cl))))
    assert fake.ragged
    with pytest.raises(ValueError, match="registry channel name or None"):
        run_cohort(fake, do_eval=False)


def test_ragged_exact_capability():
    assert chan.ragged_exact(None)
    assert chan.ragged_exact("exp_iid_csi")
    assert not chan.ragged_exact("pathloss")
    assert not chan.ragged_exact(
        ImperfectCSI(make_channel("pathloss", 4), eps=0.1))
