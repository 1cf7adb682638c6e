"""Thin wrappers over the jax API surface the runtime and tests share.

Each one states what the installed jax does, so call sites stay short:
``jax.shard_map`` with replication checking off by default, meshes with
explicit ``Auto`` axis types, and the sharding-invariant threefry RNG.
"""
from __future__ import annotations

import jax
from jax import lax


def axis_size(axis: str) -> int:
    """Size of the named mesh axis inside a ``shard_map`` body."""
    return lax.axis_size(axis)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def partitionable_rng():
    """Context manager forcing the sharding-invariant threefry
    implementation, so sharded param init produces the same values
    regardless of output shardings."""
    return jax.threefry_partitionable(True)


def shard_map(fn, mesh, in_specs, out_specs, *, check: bool = False):
    """``jax.shard_map`` with replication checking off by default (our
    manual collectives intentionally produce device-varying
    intermediates)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
