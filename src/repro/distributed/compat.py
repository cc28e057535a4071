"""The sharding API spellings the repo uses, in one place.

* ``make_mesh(shape, axes)`` — every axis ``AxisType.Auto`` (the compiler
  propagates shardings; nothing here uses explicit-sharding mode).
* ``shard_map(...)`` — ``jax.shard_map`` with replication checking off by
  default: the Pallas kernels inside have no replication rule.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map"]


def make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
