"""Mesh and ``shard_map`` helpers shared by src/, tests/ and benchmarks/.

Every mesh construction goes through :func:`make_mesh`, which pins the
axis types to ``Auto``, and every ``shard_map`` through :func:`shard_map`,
which turns the replication check off by default.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis typed ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
