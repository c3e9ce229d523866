"""Jit'd public wrappers around the Pallas kernels.

The one place that picks interpret mode from the backend: the kernels
compile for the chip when JAX's default backend is a TPU, and run in the
Pallas interpreter anywhere else (the CPU tests).  The kernel functions
themselves take ``interpret`` with no default.
"""

from __future__ import annotations

import jax

from repro.kernels import inflota_search as _search
from repro.kernels import ota_round as _round
from repro.kernels import ota_transmit as _ota


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def ota_round(w, h, w_abs, eta, noise, k_eff, k_i, p_max, numer,
              *, h_est=None, L, sigma2, block_d: int = 1024,
              interpret: bool | None = None):
    """Fused search + transmit single-pass round (see kernels.ota_round).

    ``h`` is the true channel the MAC applies; the optional ``h_est`` is
    the traced CSI estimate the search/transmit inversion uses
    (imperfect-CSI scenarios; None = perfect CSI).  ``L`` / ``sigma2``
    may be traced scalars (a VMEM row operand — sweeping them never
    recompiles the kernel).
    """
    if interpret is None:
        interpret = _default_interpret()
    return _round.ota_round(
        w, h, w_abs, eta, noise, k_eff, k_i, p_max, numer, h_est=h_est,
        L=L, sigma2=sigma2, block_d=block_d, interpret=interpret)


def ota_shard_tx(w, h, h_est, cw, s, b, k_eff, k_i, p_max, wmask=None,
                 *, y, block_d: int = 16384, interpret: bool | None = None):
    """One worker-shard block's fused transmit added onto the carried
    superposition ``y`` (see kernels.ota_round.ota_shard_tx): the
    (U_b, D) beta tile is rebuilt in VMEM from the rank-1 ``(cw, s)``
    factorization and only the updated (D,) ``y`` leaves the kernel.
    ``k_i`` (the true sample counts) is taken with the block's other
    per-worker columns and not read: the beta reductions that weigh by
    it are the engine's."""
    del k_i
    if interpret is None:
        interpret = _default_interpret()
    return _round.ota_shard_tx(
        w, h, h_est, cw, s, b, k_eff, p_max, wmask, y=y,
        block_d=block_d, interpret=interpret)


def ota_aggregate(w, h, beta, b, noise, k_i, p_max,
                  block_d: int = 1024, interpret: bool | None = None,
                  h_est=None):
    """Fused OTA transmit/aggregate/post-process (see kernels.ota_transmit)."""
    if interpret is None:
        interpret = _default_interpret()
    return _ota.ota_transmit_aggregate(
        w, h, beta, b, noise, k_i, p_max, h_est=h_est,
        block_d=block_d, interpret=interpret)


def inflota_search(h, w_abs, k_i, p_max, *, eta, numer, L, sigma2,
                   block_d: int = 1024, interpret: bool | None = None):
    """Fused Theorem-4 line search (see kernels.inflota_search).

    ``eta`` / ``numer`` / ``L`` / ``sigma2`` may all be traced.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _search.inflota_search(
        h, w_abs, k_i, p_max, eta=eta, numer=numer,
        L=L, sigma2=sigma2, block_d=block_d, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    blk_q: int = 128, blk_k: int = 256,
                    interpret: bool | None = None):
    """Fused causal GQA attention (see kernels.flash_attention)."""
    from repro.kernels import flash_attention as _fa
    if interpret is None:
        interpret = _default_interpret()
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, blk_q=blk_q, blk_k=blk_k,
                               interpret=interpret)
