"""Mesh construction over the visible devices.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state: tests force host device counts before building
one.

Spatial axes: ``make_local_mesh`` accepts ``spatial=((name, size), ...)``
so a ``ParallelPlan`` (DESIGN.md §5) referencing named spatial axes can be
instantiated without ad-hoc ``compat.make_mesh`` calls; ``make_plan_mesh``
builds the mesh straight from a plan's recorded axis degrees.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax

from repro.core import compat

SpatialAxes = Sequence[Tuple[str, int]]


def make_local_mesh(model: int = 1, data: int = 1, *,
                    spatial: SpatialAxes = ()):
    """Mesh over however many (possibly forced-host) devices exist.

    ``spatial`` appends named spatial axes after ``data``/``model`` —
    pass ``model=1`` (the default) when a plan's axes replace the legacy
    ``model`` spatial axis entirely."""
    shape = (data, model) + tuple(s for _, s in spatial)
    axes = ("data", "model") + tuple(a for a, _ in spatial)
    return compat.make_mesh(shape, axes)


def make_plan_mesh(plan, *, extra: SpatialAxes = ()):
    """Mesh with exactly the axes (and degrees) a ``ParallelPlan``
    records, in plan order, plus any ``extra`` trailing axes. For a
    pipelined plan this is group 0's mesh (the plan's degrees are per
    group) — ``make_pipeline_meshes`` builds the full set."""
    pairs = tuple(plan.mesh_axes) + tuple(extra)
    if getattr(plan, "n_groups", 1) > 1:
        return make_pipeline_meshes(plan)[0]
    return compat.make_mesh(tuple(s for _, s in pairs),
                            tuple(a for a, _ in pairs))


def make_pipeline_meshes(plan) -> Tuple[jax.sharding.Mesh, ...]:
    """One mesh per pipeline device group (DESIGN.md §13): group ``g``
    owns devices ``[g*d, (g+1)*d)`` of ``jax.devices()`` where ``d`` is
    the product of the plan's per-group axis degrees — disjoint,
    equal-sized slices in device order, so group 0's mesh coincides with
    the devices ``make_plan_mesh`` would pick for the degenerate
    single-group case (checkpoint restore and eval reuse it)."""
    import numpy as np

    d = 1
    for _, s in plan.mesh_axes:
        d *= s
    n_groups = plan.n_groups
    devices = jax.devices()
    if n_groups * d > len(devices):
        raise ValueError(
            f"plan {plan.name!r} needs {n_groups} groups x {d} devices "
            f"but only {len(devices)} are visible")
    shape = tuple(s for _, s in plan.mesh_axes)
    axes = tuple(a for a, _ in plan.mesh_axes)
    return tuple(
        jax.sharding.Mesh(
            np.asarray(devices[g * d:(g + 1) * d]).reshape(shape), axes)
        for g in range(n_groups))
