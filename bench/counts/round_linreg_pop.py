"""Operations and bytes one round of the Sec. VI-A population needs,
whatever kernels or fusions compute it (f32 throughout).

Work: every worker's full-batch gradient step on its K_i real samples
(the 1-neuron model y = w2 (w1 x + b1): 5 FLOPs forward, 10 backward per
sample), the O(U log U) Theorem-4 search (thresholds and their sort
U log2 U, prefix sums U, one lookup per candidate U log2 U, then the U
curves R_t over D entries, 8 U D, and their argmin U D), and the
transmit (``ota_shard_tx``'s count over all U workers) with the descale.

Bytes are what must cross HBM at least once: the padded worker data
(inputs, targets and mask, U k_max each), the per-worker counts, the
parameters and the per-entry inputs.  The local updates (U x D) and the
selection are intermediates and not counted.
"""

import math

F32 = 4


def counts(U: int, K_real: float, k_max: int, D: int) -> dict:
    from bench.counts import ota_shard_tx
    logu = math.log2(max(U, 2))
    local = 15 * U * K_real
    search = 2 * U * logu + U + 9 * U * D
    flops = local + search + ota_shard_tx.counts(U, D)["flops"] + 3 * D
    nbytes = F32 * (3 * U * k_max + U + 4 * D)
    return {"flops": float(flops), "bytes": float(nbytes)}
