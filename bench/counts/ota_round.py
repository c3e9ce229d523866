"""Operations and bytes of one ``ota_round`` call for one experiment:
the Theorem-4 line search and the analog transmit of a rank-1 round over
U workers and D entries (f32), as the algorithm needs them.

FLOPs: candidates (eq. 43) 4 U D; per candidate k of U, the feasibility
test U D, the denominator sum 2 U D and R_t with its argmin update 12 D;
transmit (eqs. 6-9): |K b w / h| 4 U D, the clipped signal 3 U D, the
superposition 2 U D, the three per-entry sums 6 U D, the descale 3 D.
Bytes: read w (U D) once, the per-entry inputs |w|, eta and the noise
(3 D), the per-worker gains, counts and budgets (5 U); write w_hat, b
and the three per-entry sums (5 D).  The selection beta is not counted:
it is an intermediate the round never needs in memory.
"""

F32 = 4


def counts(U: int, D: int) -> dict:
    flops = 4 * U * D + U * (3 * U * D + 12 * D) + 18 * U * D + 3 * D
    nbytes = F32 * (U * D + 3 * D + 5 * U + 5 * D)
    return {"flops": float(flops), "bytes": float(nbytes)}
