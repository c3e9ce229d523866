"""The benchmark's own copy of the paper's Sec. VI data generators.

The plain references must not import the system under test, yet they
have to train on the very samples the system builds from a data seed.
These functions repeat the NumPy draws of the system's task data
(``sample_counts``, ``partition``, ``mnist_like``) draw for draw, so the
same seed gives the same arrays byte for byte.
"""

from __future__ import annotations

import numpy as np


def sample_counts(U: int, k_bar: int, spread: int = 5,
                  seed: int = 0) -> np.ndarray:
    """Per-worker sample counts K_i ~ round(U[k_bar - 5, k_bar + 5])."""
    rng = np.random.default_rng(seed)
    return np.round(rng.uniform(k_bar - spread, k_bar + spread,
                                size=U)).astype(int).clip(1)


def mnist_like(n: int, seed: int = 0, n_classes: int = 10,
               dim: int = 784, noise: float = 1.5,
               label_noise: float = 0.07):
    """784-dim, 10-class cluster images in [0, 1] with 7% flipped labels."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(n_classes, dim)) * 0.8
    labels = rng.integers(0, n_classes, size=n)
    x = protos[labels] + noise * rng.normal(size=(n, dim))
    flip = rng.uniform(size=n) < label_noise
    labels = np.where(flip, rng.integers(0, n_classes, size=n), labels)
    x = 1.0 / (1.0 + np.exp(-x))
    return x.astype(np.float32), labels.astype(np.int32)


def partition(x, y, counts, seed: int = 0):
    """IID split of (x, y) into workers of the given sample counts."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    total = int(np.sum(counts))
    idx = rng.permutation(n) if total <= n else rng.integers(0, n, total)
    out, ofs = [], 0
    for k in counts:
        sel = idx[ofs:ofs + k]
        out.append((x[sel], y[sel]))
        ofs += k
    return out


def padded(workers):
    """Workers -> (X, Y, mask, k_i), padded to the largest worker."""
    sizes = np.asarray([x.shape[0] for x, _ in workers])
    k_max = int(sizes.max())

    def pad(a):
        return np.concatenate(
            [a, np.zeros((k_max - a.shape[0],) + a.shape[1:], a.dtype)])

    X = np.stack([pad(x) for x, _ in workers])
    Y = np.stack([pad(y) for _, y in workers])
    mask = (np.arange(k_max)[None, :] < sizes[:, None]).astype(np.float32)
    return X, Y, mask, sizes.astype(np.float32)


def mlp_task(U: int, k_bar: int, data_seed: int, n_test: int = 2000):
    """The Sec. VI-B MLP task: padded worker arrays and the test split."""
    counts = sample_counts(U, k_bar, seed=data_seed)
    x, y = mnist_like(int(np.sum(counts)) + n_test, seed=data_seed)
    workers = partition(x[:-n_test], y[:-n_test], counts, seed=data_seed)
    return padded(workers), (x[-n_test:], y[-n_test:])
