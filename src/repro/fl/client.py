"""Worker-side computation: local model update (paper eq. (4)).

Full-batch GD by default; mini-batch SGD when ``k_b`` is given (paper
Sec. IV-C).  One gradient step per round, as in Algorithm 1 line 4.

Minibatch draws are RESTRICTION-STABLE: each sample's selection priority
derives from ``fold_in(key, sample_index)``, so a worker padded from K_i
to any larger K_max (ragged sweep cohorts) draws exactly the samples —
in exactly the order — its standalone run would.  This is the same
per-index-key rule the worker axis uses (``repro.core.channel``), and it
is what lets ``k_b`` / SGD cells join ragged cohort merges bit-exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def minibatch_indices(key, mask, k_b: int) -> jax.Array:
    """``k_b`` indices drawn uniformly without replacement from the real
    samples of a (possibly padded) block.

    Every sample gets a priority ``uniform(fold_in(key, i))`` — a
    function of the key and the sample's INDEX only — and the ``k_b``
    smallest-priority real samples win (padding is pushed to +inf).
    Uniformity: the priorities of the real samples are iid continuous,
    so their ranking is a uniform random permutation and its first
    ``k_b`` elements are a uniform without-replacement draw.  Stability:
    growing the block adds only +inf priorities, leaving both the chosen
    set and its order untouched — unlike ``jax.random.choice``, whose
    draw depends on the block length.
    """
    k_max = mask.shape[0]
    pri = jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(
            jnp.arange(k_max))
    pri = jnp.where(mask > 0, pri, jnp.inf)
    return jnp.argsort(pri)[:k_b]


def local_update(task, params, x, y, lr: float, *, key=None,
                 k_b: int | None = None, steps: int = 1):
    """Returns the worker's updated local parameters w_i (pytree)."""
    def one_step(p, k):
        if k_b is not None:
            idx = minibatch_indices(k, jnp.ones((x.shape[0],)), k_b)
            xb, yb = x[idx], y[idx]
        else:
            xb, yb = x, y
        g = jax.grad(task.loss)(p, xb, yb)
        return jax.tree.map(lambda w, gg: w - lr * gg, p, g)

    p = params
    keys = jax.random.split(key, steps) if key is not None else [None] * steps
    for s in range(steps):
        p = one_step(p, keys[s])
    return p


def local_grad_masked(task, params, x, y, mask, *, key,
                      k_b: int | None = None):
    """The gradient of one masked local step over a K_max-padded sample
    block (one worker): of the mask-weighted mean loss over the real
    samples, or over ``k_b`` of them drawn with ``key``.  See
    ``local_update_masked``, which steps with it."""
    def masked_loss(p, xb, yb, mb):
        per = jax.vmap(lambda xi, yi: task.loss(p, xi[None], yi[None]))(
            xb, yb)
        return jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb), 1.0)

    if k_b is not None:
        # restriction-stable draw over the worker's real samples only
        idx = minibatch_indices(key, mask, k_b)
        xb, yb = x[idx], y[idx]
        mb = jnp.ones((k_b,), mask.dtype)
    else:
        xb, yb, mb = x, y, mask
    return jax.grad(masked_loss)(params, xb, yb, mb)


def local_update_masked(task, params, x, y, mask, lr: float, *, key,
                        k_b: int | None = None, steps: int = 1):
    """Masked local update over a K_max-padded sample block (one worker).

    Uniform shapes across workers are what make the round engine
    vmap-batchable: every worker's data is padded to the fleet-wide K_max
    along axis 0 and ``mask`` (K_max,) flags the real samples.  The
    gradient of the mask-weighted mean loss over the padded block equals
    the plain mean-loss gradient over the worker's true K_i samples, so
    this is a drop-in for ``local_update`` under ``jax.vmap``.

    ``task.loss`` is only assumed to be a mean of per-sample losses (true
    for every TaskModel here); it is re-weighted by evaluating it per
    sample under an inner vmap.  Step ``s`` draws with
    ``split(key, steps)[s]``.
    """
    p = params
    keys = jax.random.split(key, steps)
    for s in range(steps):
        g = local_grad_masked(task, p, x, y, mask, key=keys[s], k_b=k_b)
        p = jax.tree.map(lambda w, gg: w - lr * gg, p, g)
    return p
