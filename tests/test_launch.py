"""launch/: mesh plans, abstract specs, train-step smoke, roofline parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core.objectives import Case
from repro.fl.dist import OTAConfig
from repro.launch import mesh as mesh_lib
from repro.launch import roofline
from repro.launch import steps as steps_lib
from repro.models.api import Model
from repro.models.config import ShapeConfig
from repro.optim import optimizers
from repro.sharding import params as psh

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------- mesh plans

class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_plan_small_arch_uses_all_batch_axes():
    cfg = registry.get_config("qwen2-0.5b")
    plan = steps_lib.plan_for(cfg, _FakeMesh({"pod": 2, "data": 16,
                                              "model": 16}))
    assert plan.worker_axes == ("pod", "data")
    assert plan.fsdp_axes == ()


def test_plan_big_arch_uses_pod_workers_and_fsdp():
    cfg = registry.get_config("arctic-480b")
    plan = steps_lib.plan_for(cfg, _FakeMesh({"pod": 2, "data": 16,
                                              "model": 16}))
    assert plan.worker_axes == ("pod",)
    assert plan.fsdp_axes == ("data",)
    # single pod: no worker axis at all -> exact-FedAvg FSDP baseline
    plan1 = steps_lib.plan_for(cfg, _FakeMesh({"data": 16, "model": 16}))
    assert plan1.worker_axes == ()
    assert plan1.fsdp_axes == ("data",)


# --------------------------------------------------------- divisibility

def test_filter_divisible_drops_odd_vocab():
    mesh = _FakeMesh({"data": 16, "model": 16})
    specs = {"w": P("model", None)}
    shapes = {"w": jax.ShapeDtypeStruct((51865, 512), jnp.float32)}
    out = psh.filter_divisible(specs, shapes, mesh)
    assert out["w"] == P(None, None)
    shapes2 = {"w": jax.ShapeDtypeStruct((51840, 512), jnp.float32)}
    assert psh.filter_divisible(specs, shapes2, mesh)["w"] == \
        P("model", None)


def test_fsdp_specs_shard_a_replicated_dim():
    cfg = registry.reduced(registry.get_config("qwen2-0.5b"))
    model = Model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    sp = psh.param_specs(shapes, fsdp_axes=("data",))
    leaves = jax.tree.leaves(sp, is_leaf=lambda x: isinstance(x, P))

    def has_data(spec):
        return any(e == "data" or (isinstance(e, tuple) and "data" in e)
                   for e in spec)
    assert any(has_data(s) for s in leaves)


# ----------------------------------------------------- train-step smoke

@pytest.mark.parametrize("policy", ["inflota", "random", None])
def test_train_step_smoke(policy):
    cfg = registry.reduced(registry.get_config("qwen2-0.5b"))
    model = Model(cfg)
    mesh = mesh_lib.make_smoke_mesh()
    plan = steps_lib.plan_for(cfg, mesh)
    opt = optimizers.adamw(1e-3)
    ota = OTAConfig(policy=policy, case=Case.GD_NONCONVEX) if policy \
        else None
    step = steps_lib.make_train_step(model, mesh, plan, opt, ota_cfg=ota)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        opt_state = opt.init(params)
        batch = registry.make_batch(cfg, ShapeConfig("t", 32, 4, "train"))
        p2, _, m = jax.jit(step)(params, opt_state, batch,
                                 jax.random.PRNGKey(1), jnp.int32(0))
    assert np.isfinite(float(m["loss"]))
    # parameters actually moved
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     params, p2)
    assert max(jax.tree.leaves(d)) > 0.0


def test_train_step_ota_noise_free_matches_fedavg():
    """With sigma2=0, h=const, all selected: OTA == exact data-parallel."""
    from repro.core.channel import ChannelConfig
    cfg = registry.reduced(registry.get_config("qwen2-0.5b"))
    model = Model(cfg)
    mesh = mesh_lib.make_smoke_mesh()
    plan = steps_lib.plan_for(cfg, mesh)
    opt = optimizers.sgd(1e-2)
    ota = OTAConfig(policy="perfect", channel=ChannelConfig(sigma2=0.0))
    s_ota = steps_lib.make_train_step(model, mesh, plan, opt, ota_cfg=ota)
    s_ref = steps_lib.make_train_step(model, mesh, plan, opt, ota_cfg=None)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        batch = registry.make_batch(cfg, ShapeConfig("t", 32, 4, "train"))
        key = jax.random.PRNGKey(1)
        pa, _, _ = jax.jit(s_ota)(params, opt.init(params), batch, key,
                                  jnp.int32(0))
        pb, _, _ = jax.jit(s_ref)(params, opt.init(params), batch, key,
                                  jnp.int32(0))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------- roofline

def test_roofline_counts_scan_trips():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=24)
        return y.sum()

    c = jax.jit(f).lower(jnp.ones((8, 64)), jnp.ones((64, 64))).compile()
    an = roofline.analyze_hlo(c.as_text())
    assert an.flops == pytest.approx(24 * 2 * 8 * 64 * 64, rel=0.05)


def test_roofline_collective_payloads():
    hlo = """
HloModule m

ENTRY %main (p0: f32[64,4]) -> f32[64,4] {
  %p0 = f32[64,4]{1,0} parameter(0)
  %ar = f32[64,4]{1,0} all-reduce(%p0), replica_groups=[2,4]<=[8], to_apply=%add
  %ag = f32[64,4]{1,0} all-gather(%p0), replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %out = f32[64,4]{1,0} add(%ar, %ag)
}
"""
    an = roofline.analyze_hlo(hlo)
    size = 64 * 4 * 4
    assert an.collectives["all-reduce"] == pytest.approx(
        2 * size * 3 / 4)
    assert an.collectives["all-gather"] == pytest.approx(size * 1 / 2)


def test_mesh_from_spec():
    m = mesh_lib.make_mesh_from_spec
    with pytest.raises(ValueError):
        m("16")


def test_roofline_peaks_keyed_by_device_kind():
    pk = roofline.peaks("TPU v5 lite")
    assert (pk.flops, pk.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no peak table"):
        roofline.peaks("cpu")
