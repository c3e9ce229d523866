"""Synthetic datasets for the paper's experiments + the LM data pipeline.

* linreg:   the paper's Sec. VI-A setup — x ~ U[0,1], y = -2x + 1 + 0.4 n.
* mnist_like: real MNIST is not downloadable in this offline container; we
  generate a 784-dim 10-class cluster dataset with the same tensor shapes
  (28x28 flattened inputs, labels 0-9) so the paper's 784-64-10 MLP and all
  *comparative* claims can be validated.  Clusters are random prototype
  images + pixel noise, linearly separable only partially (test accuracy
  saturates < 100%, like MNIST).
* token_stream: deterministic synthetic token batches for LM training.
* markov_tokens: sequences from a fixed sparse Markov source over a
  vocabulary, the FL language-model task's data (a next-token CE that
  can fall, which uniform random ids would not give).
"""

from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np


def linreg(n: int, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, 1.0, size=(n, 1))
    y = -2.0 * x + 1.0 + 0.4 * rng.normal(size=(n, 1))
    return x.astype(np.float32), y.astype(np.float32)


def mnist_like(n: int, seed: int = 0, n_classes: int = 10,
               dim: int = 784, noise: float = 1.5,
               label_noise: float = 0.07):
    """10-class cluster images; ~7% flipped labels keep test accuracy
    below 100% (like MNIST's hard digits) so policy gaps stay visible."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(n_classes, dim)) * 0.8
    labels = rng.integers(0, n_classes, size=n)
    x = protos[labels] + noise * rng.normal(size=(n, dim))
    flip = rng.uniform(size=n) < label_noise
    labels = np.where(flip, rng.integers(0, n_classes, size=n), labels)
    # squash to [0, 1] like pixel intensities
    x = 1.0 / (1.0 + np.exp(-x))
    return x.astype(np.float32), labels.astype(np.int32)


def token_stream(batch: int, seq: int, vocab: int,
                 seed: int = 0) -> Iterator[dict]:
    """Deterministic pseudo-text stream: Zipfian unigrams + a short-range
    bigram structure so the LM loss actually decreases during training."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    shift = rng.integers(1, vocab)
    while True:
        base = rng.choice(vocab, size=(batch, seq), p=probs)
        # every even position strongly predicts the next token
        nxt = (base * 31 + shift) % vocab
        toks = base.copy()
        toks[:, 1::2] = nxt[:, 0::2][:, :toks[:, 1::2].shape[1]]
        yield {"tokens": toks.astype(np.int32),
               "labels": toks.astype(np.int32)}


def markov_tokens(n_seq: int, seq_len: int, vocab: int, seed: int = 0,
                  fanout: int = 4, source_seed: int = 0) -> np.ndarray:
    """(n_seq, seq_len) int32 ids from a fixed sparse Markov source.

    The source (``source_seed``) gives every id ``fanout`` successors
    with Dirichlet(1) probabilities; ``seed`` draws each sequence's first
    id uniformly and its later ids from the source, all sequences
    stepped together.
    """
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
