"""The Moonlight cell at a test's size (hidden 64, 2 heads, 16 experts of
which 8 are held, vocabulary 256, 3 layers, U = 4, T = 32; Pallas in CPU
interpret mode): the round against the plain reference, the bfloat16
control and planted faults reading incorrect, the counts and each
reader the cell reports (the population cell's kernel-time, non-kernel
and idle readers among them)."""

import importlib.util
import os

import pytest

from bench.tests import helpers

SMALL = {"hidden_size": 64, "num_attention_heads": 2,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "kv_lora_rank": 32, "router_experts": 16, "n_routed_experts": 8,
         "moe_intermediate_size": 24, "intermediate_size": 96,
         "num_experts_per_tok": 2, "vocab_size": 256,
         "num_hidden_layers": 3, "k_bar": 6, "lr": 0.5}
TRAFFIC = {"U": 4, "seq_len": 32}
CELL = "moonlight_16b_a3b.round_u20"


def _cell():
    ctx, gen = helpers.context(CELL, config=SMALL, traffic=TRAFFIC)
    from bench.reference import moonlight_fl
    ctx.config["D"] = moonlight_fl.param_count(gen.shape_of(ctx.config))
    return ctx, gen


def test_the_published_share_counts_d():
    from bench.reference import moonlight_fl
    ctx, gen = helpers.context(CELL)
    assert moonlight_fl.param_count(gen.shape_of(ctx.config)) \
        == ctx.config["D"] == 568_484_352


def test_round_matches_the_reference_and_the_control_does_not():
    ctx, gen = _cell()
    state = gen.setup(ctx)
    answers = gen.window(ctx, state, 0.0)
    e2e = gen.end_to_end(ctx, state, answers)
    gen.release(ctx, state)
    assert answers["rounds"] == 1 and e2e["pop_round_s"] > 0
    assert ctx.window["expert_load_max"] >= 1.0
    checks = gen.check(ctx, answers)
    assert helpers.correct(checks), checks
    ctl = gen.check(ctx, gen.control_answers(ctx, None, answers))
    assert not helpers.correct(ctl), ctl


def _scaled_answer(monkeypatch):
    from repro.fl import worker_shard
    real = worker_shard.build_sharded_engine

    def build(*a, **k):
        eng = real(*a, **k)

        def step(state, _=None):
            new, stats = eng.step(state, _)
            return new._replace(flat=new.flat * (1 + 1e-3)), stats
        return eng._replace(step=step)
    monkeypatch.setattr(worker_shard, "build_sharded_engine", build)


def _one_expert_dropped(monkeypatch):
    """The last held expert's routed tokens left out of the layer."""
    import jax.numpy as jnp

    from repro.fl import lm_models
    real = lm_models.held_experts

    def held(x, ids, w, experts, shape):
        keep = shape.expert_offset + shape.n_held - 1
        return real(x, ids, jnp.where(ids == keep, 0.0, w), experts, shape)
    monkeypatch.setattr(lm_models, "held_experts", held)


@pytest.mark.parametrize("plant", [_scaled_answer, _one_expert_dropped])
def test_fault_reads_incorrect(monkeypatch, plant):
    ctx, gen = _cell()
    plant(monkeypatch)
    _, checks, ok = helpers.run(ctx, gen)
    assert not ok, checks


def test_counts_at_the_cell():
    """35.8 TFLOP a round at the published widths: 6 x 275.6 M active
    parameters x 20,480 tokens, 1.65 TFLOP of attention, the search and
    the transmit; the D-streams bind the bytes."""
    from bench.counts import round_moonlight
    ctx, gen = helpers.context(CELL)
    shape = gen.shape_of(ctx.config)
    assert round_moonlight.active_params(shape) == pytest.approx(
        275_644_416, abs=1)
    n = round_moonlight.counts(shape, 20, 1, 1024, ctx.config["D"])
    assert n["flops"] == pytest.approx(35.79e12, rel=1e-3)
    assert n["bytes"] == 4 * 23 * ctx.config["D"]
    tx = round_moonlight.transmit(1, ctx.config["D"])
    assert tx["bytes"] == 4 * (5 * ctx.config["D"] + 6)


class _Ctx:
    def __init__(self, window, ops, config, traffic):
        self.window = window
        self.reduced = {"ops": ops, "busy_s": 2.0, "window_s": 4.0}
        self.devices = [object()]
        self.config, self.traffic = config, traffic

    def peak(self):
        return {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    path = os.path.join(helpers.ROOT, "bench", "layer_metrics",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(name, ops, window=None):
    ctx, _ = helpers.context(CELL)
    return _reader(name).read(_Ctx(window or {"rounds": 4}, ops,
                                   ctx.config, ctx.traffic))


def test_readers_on_a_fabricated_trace():
    from bench.counts import round_moonlight
    ctx, gen = helpers.context(CELL)
    D = ctx.config["D"]
    ops = {"ota_shard_tx.3": 1.2, "fusion.7": 0.4}
    assert _read("ota_shard_tx_us.pop", ops) == pytest.approx(3e5)
    least = 20 * round_moonlight.transmit(1, D)["bytes"] / 819e9
    assert _read("ota_shard_tx_roofline.moonlight", ops) == pytest.approx(
        100 * least / 0.3)
    assert _read("nonkernel_busy_ms.pop", ops) == pytest.approx(200.0)
    assert _read("idle_share.pop", ops) == pytest.approx(50.0)
    n = round_moonlight.counts(gen.shape_of(ctx.config), 20, 1, 1024, D)
    assert _read("round_mfu.moonlight", ops) == pytest.approx(
        100 * 4 * n["flops"] / 197e12 / 2.0)
    assert _read("expert_load_max.moonlight", ops,
                 {"rounds": 4, "expert_load_max": 1.75}) == 1.75


def test_readers_report_nothing_without_their_source():
    ops = {"fusion.7": 0.4}
    assert _read("ota_shard_tx_us.pop", ops) is None
    assert _read("ota_shard_tx_roofline.moonlight", ops) is None
    assert _read("expert_load_max.moonlight", ops) is None
