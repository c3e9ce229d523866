"""Operations and bytes one experiment-round of the Sec. VI-B MLP needs,
whatever kernels or fusions compute it (f32 throughout).

Work: U workers' local SGD step on k_b samples each (2 P FLOPs forward
and 4 P backward per sample, with P = d_in h + h c the weights), the
evaluation of the new parameters on the n_test test samples (2 P each),
and the aggregation: for ``inflota`` the Theorem-4 search and transmit
(``ota_round``'s count), for ``random`` the transmit alone (18 U D), for
``perfect`` the error-free weighted average (2 U D).

Bytes are what must cross HBM at least once: the k_b sampled inputs and
labels of every worker, the test split, the parameters read twice (this
round and the last one, for eta) and written once, and the noise.  Local
updates (U x D) and the selection are intermediates and not counted.
"""

F32 = 4


def counts(U: int, k_b: int, d_in: int, hidden: int, classes: int,
           n_test: int, D: int, policy: str) -> dict:
    from bench.counts import ota_round
    P = d_in * hidden + hidden * classes
    aggregate = {"inflota": ota_round.counts(U, D)["flops"],
                 "random": 18 * U * D, "perfect": 2 * U * D}[policy]
    flops = U * k_b * 6 * P + n_test * 2 * P + aggregate
    nbytes = F32 * (U * k_b * (d_in + 1) + n_test * (d_in + 1) + 4 * D)
    return {"flops": float(flops), "bytes": float(nbytes)}
