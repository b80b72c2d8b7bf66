"""shard_map and mesh-axis spellings used across the repo.

``shard_map_nocheck`` is ``jax.shard_map`` with VMA checking off (the
collectives' outputs are value-identical across a graph group, which the
checker cannot prove statically); ``auto_axis_types_kw`` gives a mesh
Auto axis types.
"""
from __future__ import annotations

import functools

import jax


def shard_map_nocheck(fn=None, **kw):
    """``jax.shard_map`` with ``check_vma=False``.  Usable as decorator or
    call."""
    if fn is None:
        return functools.partial(shard_map_nocheck, **kw)
    return jax.shard_map(fn, **kw, check_vma=False)


def auto_axis_types_kw(n_axes: int) -> dict:
    """``axis_types=(Auto,)*n`` kwargs for ``jax.make_mesh``."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}
