"""Flash attention (causal GQA) as a Pallas TPU kernel.

§Perf target A's remaining bottleneck is the HBM round-trip of the
chunked-attention scores/probs (≈ T² traffic).  This kernel keeps the
whole softmax in VMEM: online max/sum recurrence over KV blocks, one
output tile per (batch, kv-head, group, q-block) grid cell.

Tiling: grid (B, n_kv, grp, T/BLK_Q); each cell streams K/V in BLK_K
slices from the (S, hd) block via an in-kernel fori_loop.  BLK_Q/BLK_K
default to 128/256 — q tile (128, hd) and k/v tiles (256, hd) fit VMEM
comfortably at hd ≤ 256 and keep the MXU dims ≥ 128-aligned.

Supports: causal masking, sliding window, logit soft-capping (gemma2) —
the attention flavours of every 'g'/'l' layer in the zoo.  Oracle:
``ref.flash_attention_ref`` (pure jnp, also the zoo's `attend` math).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, blk_k: int, seq: int,
            causal: bool, window: Optional[int], softcap: Optional[float],
            q_start_fn):
    """One (q-block) tile: online-softmax over KV blocks."""
    q = q_ref[...]                                    # (blk_q, hd)
    blk_q, hd = q.shape
    qi = q_start_fn()                                 # scalar: first q row
    scale = 1.0 / math.sqrt(hd)

    n_kv_blocks = pl.cdiv(seq, blk_k)

    def body(i, carry):
        acc, m_i, l_i = carry
        k = k_ref[pl.ds(i * blk_k, blk_k), :]
        v = v_ref[pl.ds(i * blk_k, blk_k), :]
        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = qi + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = i * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((blk_q, hd), jnp.float32)
    m0 = jnp.full((blk_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, n_kv_blocks, body, (acc0, m0, l0))
    # rows with no live key (shouldn't happen under causal self-attn)
    l_safe = jnp.where(l_i > 0, l_i, 1.0)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    blk_q: int = 128, blk_k: int = 256,
                    interpret: bool):
    """q: (B, T, nq, hd); k/v: (B, S, n_kv, hd) -> (B, T, nq, hd).

    GQA: query head g of group k attends with kv head k (nq = n_kv · grp).
    """
    B, T, nq, hd = q.shape
    S, n_kv = k.shape[1], k.shape[2]
    grp = nq // n_kv
    blk_q = min(blk_q, T)
    blk_k = min(blk_k, S)
    pad_t = (-T) % blk_q
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = q.shape[1]
    pad_s = (-S) % blk_k
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    Sp = k.shape[1]

    # (B, T, n_kv, grp, hd) -> grid over (B, n_kv, grp, q-blocks)
    qg = q.reshape(B, Tp, n_kv, grp, hd)

    grid = (B, n_kv, grp, Tp // blk_q)

    def q_start():
        return pl.program_id(3) * blk_q

    kern = functools.partial(
        _kernel, blk_k=blk_k, seq=S, causal=causal, window=window,
        softcap=softcap, q_start_fn=q_start)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, blk_q, None, None, hd),
                         lambda b, h, g, i: (b, i, h, g, 0)),
            pl.BlockSpec((None, Sp, None, hd),
                         lambda b, h, g, i: (b, 0, h, 0)),
            pl.BlockSpec((None, Sp, None, hd),
                         lambda b, h, g, i: (b, 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((None, blk_q, None, None, hd),
                               lambda b, h, g, i: (b, i, h, g, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, n_kv, grp, hd), q.dtype),
        interpret=interpret,
    )(qg, k, v)
    out = out.reshape(B, Tp, nq, hd)
    if pad_t:
        out = out[:, :T]
    return out
