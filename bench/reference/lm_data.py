"""The benchmark's own copy of the FL language-model task's data.

Repeats the NumPy draws of the system's ``moonlight_lm`` task data
(``repro.data.synthetic.markov_tokens`` over the worker counts of
``sample_counts``) draw for draw, so the same data seed gives the same
token arrays byte for byte without importing the system under test.
"""

from __future__ import annotations

import numpy as np

from bench.reference.data import sample_counts


def markov_tokens(n_seq: int, seq_len: int, vocab: int, seed: int = 0,
                  fanout: int = 4, source_seed: int = 0) -> np.ndarray:
    """(n_seq, seq_len) int32 ids from a fixed sparse Markov source: every
    id has ``fanout`` successors with Dirichlet(1) probabilities."""
    src = np.random.default_rng(source_seed)
    succ = src.integers(0, vocab, size=(vocab, fanout))
    cum = np.cumsum(src.dirichlet(np.ones(fanout), size=vocab), axis=1)
    rng = np.random.default_rng(seed)
    tok = np.empty((n_seq, seq_len), np.int64)
    tok[:, 0] = rng.integers(0, vocab, size=n_seq)
    u = rng.random((n_seq, seq_len - 1))
    for t in range(1, seq_len):
        prev = tok[:, t - 1]
        j = np.minimum(np.sum(u[:, t - 1, None] > cum[prev], axis=1),
                       fanout - 1)
        tok[:, t] = succ[prev, j]
    return tok.astype(np.int32)


def lm_task(U: int, k_bar: int, data_seed: int, vocab: int,
            seq_len: int = 1024):
    """(X, Y, mask, k_i) of U workers: K_i ~ round(U[k_bar - 5,
    k_bar + 5]) sequences of ``seq_len`` input ids and their next ids,
    padded to the largest worker."""
    counts = sample_counts(U, k_bar, seed=data_seed)
    tok = markov_tokens(int(np.sum(counts)) + 8, seq_len + 1, vocab,
                        seed=data_seed)
    x, y = tok[:, :-1], tok[:, 1:]
    k_max = int(counts.max())
    X = np.zeros((U, k_max, seq_len), np.int32)
    Y = np.zeros((U, k_max, seq_len), np.int32)
    ofs = 0
    for i, k in enumerate(counts):
        X[i, :k], Y[i, :k] = x[ofs:ofs + k], y[ofs:ofs + k]
        ofs += k
    mask = (np.arange(k_max)[None, :] < counts[:, None]).astype(np.float32)
    return X, Y, mask, counts.astype(np.float32)
