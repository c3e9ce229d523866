"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Production target: TPU v5e, 256 chips per pod.
  single pod: (data=16, model=16)
  two pods:   (pod=2, data=16, model=16) = 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the code shards by
    ``NamedSharding`` / ``shard_map`` and lets XLA propagate, which the
    ``Explicit`` axes that ``jax.make_mesh`` defaults to would reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(data: int | None = None, model: int = 1):
    """A small mesh over however many (host) devices are available."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _make_mesh((data, model), ("data", "model"))


def make_mesh_from_spec(spec: str):
    """'16x16' -> (data, model); '2x16x16' -> (pod, data, model)."""
    dims = tuple(int(x) for x in spec.lower().split("x"))
    if len(dims) == 2:
        return _make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return _make_mesh(dims, ("pod", "data", "model"))
    raise ValueError(spec)
