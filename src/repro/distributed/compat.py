"""The one JAX-compatibility module, written for the installed JAX (0.9).

* :func:`shard_map` forwards to ``jax.shard_map`` (``check_vma`` turns
  the replication check on or off; ``axis_names`` lists the axes made
  manual).
* :func:`make_mesh` builds meshes with **Auto** axes. ``jax.make_mesh``
  now defaults to Explicit axes, under which
  ``jax.lax.with_sharding_constraint`` and indexing a sharded result
  raise; the model code (:class:`repro.distributed.context.DistContext`)
  relies on Auto sharding propagation.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs, check_vma=True, axis_names=None):
    kw = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))
