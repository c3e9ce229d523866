"""Shard a cohort's experiment axis across the device mesh.

A vmapped cohort is embarrassingly parallel over experiments, so the
leading axis maps straight onto the ``launch/mesh.py`` data-parallel
axes: each device runs E / n_devices whole training scans.  With one
device (the common CPU container) everything degrades to a no-op, so the
sweep engine never branches on topology.

The experiment count rarely divides the device count; ``pad_batch``
repeats the trailing experiment (wasted compute, not wrong results) and
``unpad`` slices the originals back out.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import mesh as mesh_lib
from repro.sharding import specs


def sweep_mesh(n: Optional[int] = None):
    """A 1-D data-parallel mesh for the experiment axis (None = no mesh).

    Returns None when only one device is visible — callers then skip
    device placement entirely.
    """
    avail = len(jax.devices())
    n = avail if n is None else min(n, avail)
    if n <= 1:
        return None
    return mesh_lib.make_smoke_mesh(data=n, model=1)


def local_sweep_mesh(n: Optional[int] = None):
    """Like :func:`sweep_mesh`, but over THIS PROCESS's devices only.

    Under ``jax.distributed`` every host sees the global device list, but
    the multi-host sweep runtime (``repro.runtime.multihost``) runs each
    host's cohort slice independently — a mesh spanning non-addressable
    devices would turn every cohort into a cross-process collective.
    Built directly from ``jax.local_devices()`` (``jax.make_mesh`` picks
    from the global list).  None when this host has a single device.
    """
    devs = jax.local_devices()
    n = len(devs) if n is None else min(n, len(devs))
    if n <= 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]).reshape(n, 1), ("data", "model"))


def shard_count(mesh) -> int:
    """How many ways the experiment axis splits on ``mesh``."""
    if mesh is None:
        return 1
    sizes = dict(mesh.shape)
    count = 1
    for a in specs.batch_axes(mesh):
        count *= sizes.get(a, 1)
    return max(count, 1)


def pad_batch(tree: Any, n_shards: int) -> Tuple[Any, int]:
    """Pad every leaf's leading axis to a multiple of ``n_shards``.

    Padding repeats the last experiment (cheap, shape-stable); returns
    (padded tree, original length).
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree, 0
    e = leaves[0].shape[0]
    pad = (-e) % n_shards

    def padded(x):
        if pad == 0:
            return x
        reps = np.concatenate([np.arange(e), np.full(pad, e - 1)])
        return np.asarray(x)[reps]

    return jax.tree.map(padded, tree), e


def unpad(tree: Any, e: int) -> Any:
    return jax.tree.map(lambda x: x[:e], tree)


def shard_batch(tree: Any, mesh) -> Any:
    """device_put each leaf with the leading (experiment) axis sharded
    over the mesh batch axes; a no-op when ``mesh`` is None."""
    if mesh is None:
        return tree
    axes = specs.batch_axes(mesh)
    if not axes:
        return tree

    def put(x):
        x = np.asarray(x)
        spec = P(axes, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def dispatch_sharded(batched_fn, batch: Any, mesh=None, *,
                     donate: bool = False) -> Tuple[Any, Optional[int]]:
    """Dispatch ``batched_fn`` over ``batch`` WITHOUT waiting for results.

    Returns ``(out, e)``: ``out`` holds device arrays (jax's async
    dispatch means the computation may still be running) and ``e`` is the
    original experiment count to ``unpad`` to after fetching (None = no
    padding was applied).  This is the async runtime's dispatch phase —
    the completion writer calls :func:`resolve` on another thread, so
    device compute overlaps the next cohort's trace/compile and the
    previous cohort's store I/O.

    ``donate=True`` donates the batch buffers to the computation (they
    are never reused — each cohort builds a fresh batch), bounding the
    memory held by a dispatch-ahead window; ignored on backends without
    donation support (CPU) to avoid per-dispatch XLA warnings.
    """
    donate_argnums = (0,) if donate and jax.default_backend() != "cpu" \
        else ()
    if mesh is None:
        return jax.jit(batched_fn, donate_argnums=donate_argnums)(batch), None
    # each device runs the vmapped function on its own slice of the
    # experiment axis: a Pallas (Mosaic) kernel inside cannot be
    # partitioned by the compiler, and the experiments never interact
    exp = P(specs.batch_axes(mesh))
    fn = jax.jit(jax.shard_map(batched_fn, mesh=mesh, in_specs=exp,
                               out_specs=exp, check_vma=False),
                 donate_argnums=donate_argnums)
    padded, e = pad_batch(batch, shard_count(mesh))
    placed = shard_batch(padded, mesh)
    with jax.set_mesh(mesh):
        out = fn(placed)
    return out, e


def resolve(out: Any, e: Optional[int]) -> Any:
    """Blocking fetch of a :func:`dispatch_sharded` result to host numpy
    (unpadding back to the original experiment count when sharded)."""
    out = jax.device_get(out)
    return out if e is None else unpad(out, e)
