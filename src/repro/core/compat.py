"""Thin wrappers over the jax surface the repo uses (jax 0.9).

Each helper pins the one argument convention the codebase relies on, so
call sites stay short: ``shard_map`` with the replication check off, the
static size of a named axis, and meshes with explicit ``Auto`` axes.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma=check`` (off by default): the
    conv-net train steps mix manually replicated params with sharded
    activations, which the static checker rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def axis_size(axis_name) -> int:
    """``lax.axis_size``: the static size of a named mesh axis."""
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with explicit ``Auto`` axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))
