"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

Interpret mode never checks tile alignment or VMEM limits, so the CPU
tests cannot see a kernel the chip's compiler refuses.  The TPU compiler
is installed with libtpu and compiles for a chip that is described, not
attached.  The topology is described inside a module fixture — never at
import — because only one process at a time may load libtpu, and every
test worker imports this file.  Each test asserts that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import inflota_search, ota_round, ota_transmit

U, D_MLP, E = 20, 50_890, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    # libtpu would otherwise write its logs outside the checkout
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a TPU executable compiled here could be written to the persistent
    # cache but never read back without a chip
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    # the program runs with 32-bit defaults; some test modules turn x64
    # on at import, under which Mosaic refuses the int64 index maps
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no libtpu: nothing can be described
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_x64", x64)
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compiled_kernel(fn, shapes, sharding) -> None:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _round(w, h, w_abs, eta, z, k_eff, k_i, p_max, numer, L, sigma2):
    return ota_round.ota_round(w, h, w_abs, eta, z, k_eff, k_i, p_max,
                               numer, L=L, sigma2=sigma2, interpret=False)


def _round_shapes(D, h_cols, lead=()):
    return [lead + s for s in ((U, D), (U, h_cols), (D,), (D,), (D,),
                               (U,), (U,), (U,), (), (), ())]


@pytest.mark.parametrize("D,h_cols", [
    (D_MLP, 1), (D_MLP, D_MLP), (1 << 20, 1)],
    ids=["mlp-rank1", "mlp-dense", "1M-rank1"])
def test_ota_round_compiles(one_chip, D, h_cols):
    _compiled_kernel(_round, _round_shapes(D, h_cols), one_chip)


def test_ota_round_vmapped_batched_scalars_compiles(one_chip):
    """A sweep cohort: every operand, L / sigma2 / numer included, has a
    leading experiment axis."""
    _compiled_kernel(jax.vmap(_round), _round_shapes(D_MLP, 1, (E,)),
                     one_chip)


def test_inflota_search_vmapped_batched_scalars_compiles(one_chip):
    def search(h, w_abs, k_i, p_max, eta, numer, L, sigma2):
        return inflota_search.inflota_search(
            h, w_abs, k_i, p_max, eta=eta, numer=numer, L=L,
            sigma2=sigma2, interpret=False)

    shapes = [(E,) + s for s in ((U, 1), (D_MLP,), (U,), (U,), (D_MLP,),
                                 (), (), ())]
    _compiled_kernel(jax.vmap(search), shapes, one_chip)


def _shard_tx(w, h, h_est, cw, s, b, k_eff, p_max, wmask, y):
    return ota_round.ota_shard_tx(w, h, h_est, cw, s, b, k_eff, p_max,
                                  wmask, y=y, interpret=False)


def _shard_tx_shapes(u_b, D, lead=()):
    return [lead + s for s in ((u_b, D), (u_b,), (u_b,), (u_b,), (D,),
                               (D,), (u_b,), (u_b,), (u_b,), (D,))]


@pytest.mark.parametrize("u_b", [1_000, 10_000])
@pytest.mark.parametrize("D", [2, D_MLP], ids=["linreg", "mlp"])
def test_ota_shard_tx_compiles(one_chip, u_b, D):
    """A worker block of any size fits VMEM: blocks too large for one
    grid step are tiled over the worker axis."""
    _compiled_kernel(_shard_tx, _shard_tx_shapes(u_b, D), one_chip)


@pytest.mark.parametrize("D", [512, 2048 * 11264, 20480 * 2048],
                         ids=["norm", "dense_ffn", "embedding"])
def test_ota_shard_tx_compiles_one_worker_a_block(one_chip, D):
    """Moonlight-16B-A3B's leaves at one worker a block: lane tiles of
    16,384 over tens of millions of entries."""
    _compiled_kernel(_shard_tx, _shard_tx_shapes(1, D), one_chip)


def test_ota_shard_tx_vmapped_compiles(one_chip):
    _compiled_kernel(jax.vmap(_shard_tx), _shard_tx_shapes(10_000, 2, (E,)),
                     one_chip)


def test_ota_transmit_vmapped_compiles(one_chip):
    def transmit(w, h, beta, b, z, k_i, p_max):
        return ota_transmit.ota_transmit_aggregate(
            w, h, beta, b, z, k_i, p_max, interpret=False)

    shapes = [(E,) + s for s in ((U, D_MLP), (U, 1), (U, D_MLP), (D_MLP,),
                                 (D_MLP,), (U,), (U,))]
    _compiled_kernel(jax.vmap(transmit), shapes, one_chip)
