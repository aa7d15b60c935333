"""Mesh and shard_map construction in one place.

All mesh and shard_map construction in the repo goes through this module,
so every mesh gets ``Auto`` axis types and every shard_map spells its
replication check the same way.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def auto_axis_types(n_axes: int):
    """``(AxisType.Auto,) * n_axes``."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis() or {})


def abstract_mesh(shape, axes):
    """``jax.sharding.AbstractMesh`` with Auto axis types."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=auto_axis_types(len(axes)))


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=auto_axis_types(len(axes)), **kw)
