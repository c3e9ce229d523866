"""Operations and bytes of one ``ota_shard_tx`` call: one block of U_b
workers transmitting D entries under a decided b (f32).

FLOPs: the block's beta rebuilt from the rank-1 factors (b <= c_i s (1 +
tol)) 3 U_b D, |K b w / h| 4 U_b D, the clipped signal 3 U_b D, the
superposition 2 U_b D, the three per-entry sums 6 U_b D.  Bytes: read w
(U_b D), the per-worker gains, estimates, candidates, counts, budgets
and mask (7 U_b), the per-entry s and b (2 D); write the four per-entry
partials (4 D).
"""

F32 = 4


def counts(U_b: int, D: int) -> dict:
    flops = 18 * U_b * D
    nbytes = F32 * (U_b * D + 7 * U_b + 2 * D + 4 * D)
    return {"flops": float(flops), "bytes": float(nbytes)}
