"""Flash-attention Pallas kernel vs the pure-jnp oracle (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref

jax.config.update("jax_platform_name", "cpu")

CASES = [
    # B, T, S, nq, nkv, hd, window, softcap, dtype, blk_q, blk_k
    (2, 64, 64, 4, 2, 32, None, None, jnp.float32, 32, 32),
    (1, 96, 96, 8, 8, 64, None, None, jnp.float32, 32, 64),
    (1, 100, 100, 8, 4, 32, None, 30.0, jnp.float32, 32, 32),   # pad T
    (2, 128, 128, 6, 2, 32, 48, None, jnp.float32, 64, 32),     # window
    (1, 64, 64, 2, 1, 16, None, None, jnp.bfloat16, 32, 32),    # MQA bf16
    (1, 33, 33, 4, 2, 32, 16, 50.0, jnp.float32, 32, 32),       # odd T
]


@pytest.mark.parametrize(
    "B,T,S,nq,nkv,hd,win,cap,dt,bq,bk", CASES)
def test_flash_matches_oracle(B, T, S, nq, nkv, hd, win, cap, dt, bq, bk):
    rng = np.random.default_rng(T * 7 + nq)
    q = jnp.asarray(rng.normal(size=(B, T, nq, hd)), dt)
    k = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), dt)
    v = jnp.asarray(rng.normal(size=(B, S, nkv, hd)), dt)
    got = flash_attention(q, k, v, causal=True, window=win, softcap=cap,
                          blk_q=bq, blk_k=bk, interpret=True)
    want = flash_attention_ref(q, k, v, causal=True, window=win,
                               softcap=cap)
    tol = 3e-2 if dt == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_zoo_attention():
    """Against the zoo's attend() (RoPE off by passing pre-rotated q/k)."""
    from repro.models import attention as attn
    from repro.configs import registry
    cfg = registry.reduced(registry.get_config("qwen2-0.5b"))
    B, T = 2, 64
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, nkv, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                          interpret=True)
    # zoo math: scores -> mask -> softmax -> PV (attend() internals)
    s = attn._gqa_scores(q, k, None)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask[None, None, None], s, attn.NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    want = jnp.einsum("bkgts,bskh->btkgh", p, v).reshape(B, T, -1)
    np.testing.assert_allclose(np.asarray(got.reshape(B, T, -1)),
                               np.asarray(want), rtol=3e-5, atol=3e-5)
