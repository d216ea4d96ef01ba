"""Activation resharding at parallelism-plan stage boundaries (DESIGN.md §5).

When a ``ParallelPlan`` (``core/plan.py``) changes layout between two
stages — deep CosmoFlow/U-Net layers whose spatial extents are too small
for the halo overhead — the activation tensor must move from one
partitioning to the other *inside* ``shard_map``. Three lowerings:

* **spatial -> batch** (``spatial_to_batch``): the spatial group's slabs
  are repartitioned into batch shards with ONE ``lax.all_to_all`` — each
  rank keeps ``1/n`` of its bytes and sends ``(n-1)/n``, the
  information-theoretic minimum for this permutation. Rank ``j`` of the
  axis ends up with batch chunk ``j`` at full spatial extent; subsequent
  layers run pure data parallelism over the widened batch grid with no
  redundant compute.
* **spatial -> replicated** (``spatial_to_replicated``): the legacy
  ``spatial_allgather`` fallback — every rank gathers the full tensor
  and the following layers run redundantly across the spatial group
  (normalized out of the loss via the plan's ``loss_redundancy``). Moves
  ``(n-1)`` x the local bytes; kept as the equivalence oracle for the
  ``all_to_all`` path (``spatial_to_batch_oracle`` composes it with a
  batch slice to produce bit-identical chunks).
* The **inverse** transitions (``batch_to_spatial`` — the reverse
  ``all_to_all``; ``replicated_to_spatial`` — a local slice) carry the
  U-Net decoder back up to the encoder's layout so skip connections stay
  local concats.

``apply`` lowers the delta between two ``Stage`` descriptors to the
minimal transition sequence (per spatial dim, in D/H/W order) and keeps
the per-sample id vector consistent through batch repartitions so
sample-keyed dropout masks stay mesh-shape invariant.

All functions are linear; JAX transposes ``all_to_all`` to the reverse
``all_to_all`` and ``all_gather`` to ``psum_scatter``, so the backward
pass of a planned model reshards cotangents for free.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compat
from repro.core import halo as halo_lib
from repro.obs import trace as trace_lib
from repro.core.spatial_conv import SpatialPartitioning, spatial_allgather

# Dimension indices in NDHWC (batch is 0).
_SPATIAL_DIMS = (1, 2, 3)


def spatial_to_batch(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """Repartition: spatial shards along ``dim`` -> batch shards (dim 0).

    Rank ``j`` receives batch chunk ``j`` from every rank, concatenated
    along ``dim`` in rank order — i.e. the full spatial extent for a
    ``1/n`` slice of the local batch. Requires ``batch % n == 0``.
    """
    n = compat.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[0] % n:
        raise ValueError(
            f"spatial_to_batch: local batch {x.shape[0]} not divisible by "
            f"{n}-way axis {axis_name!r}")
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=dim,
                          tiled=True)


def batch_to_spatial(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """Inverse of ``spatial_to_batch``: batch shards -> spatial slabs."""
    n = compat.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[dim] % n:
        raise ValueError(
            f"batch_to_spatial: dim {dim} extent {x.shape[dim]} not "
            f"divisible by {n}-way axis {axis_name!r}")
    return lax.all_to_all(x, axis_name, split_axis=dim, concat_axis=0,
                          tiled=True)


def spatial_to_replicated(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """Gather spatial shards to a full local copy (the blocking oracle)."""
    return halo_lib.all_gather_dim(x, axis_name, dim)


def replicated_to_spatial(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """Slice this rank's slab out of a replicated tensor (purely local)."""
    n = compat.axis_size(axis_name)
    if n == 1:
        return x
    w = x.shape[dim] // n
    idx = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(x, idx * w, w, axis=dim)


def spatial_to_batch_oracle(x: jax.Array, axis_name: str,
                            dim: int) -> jax.Array:
    """Equivalence oracle for ``spatial_to_batch``: all_gather the full
    tensor, then slice this rank's batch chunk. Moves ``n``x the bytes of
    the ``all_to_all`` lowering but lands the identical local block."""
    n = compat.axis_size(axis_name)
    if n == 1:
        return x
    full = halo_lib.all_gather_dim(x, axis_name, dim)
    chunk = x.shape[0] // n
    idx = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=0)


def shard_batch(y: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Slice the local chunk of a batch-replicated tensor after its batch
    dim was extended over ``axes`` (in transition order) — the target-side
    companion of ``spatial_to_batch`` for labels that were never spatially
    sharded (CosmoFlow regression targets)."""
    for a in axes:
        n = compat.axis_size(a)
        if n == 1:
            continue
        chunk = y.shape[0] // n
        idx = lax.axis_index(a)
        y = lax.dynamic_slice_in_dim(y, idx * chunk, chunk, axis=0)
    return y


@jax.named_scope("reshard")
def apply(
    h: jax.Array,
    src,
    dst,
    *,
    sample_ids: Optional[jax.Array] = None,
    oracle: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Reshard ``h`` from stage ``src``'s layout to stage ``dst``'s.

    ``src``/``dst`` are ``core.plan.Stage`` descriptors. Per spatial dim
    (D/H/W order) the delta lowers to exactly one transition:

    * axis leaves the spatial side and joins ``dst.batch_axes`` ->
      ``spatial_to_batch`` (or its all-gather ``oracle``), and
      ``sample_ids`` is sliced to the local chunk;
    * axis leaves the spatial side and does NOT join the batch ->
      ``spatial_to_replicated`` (legacy redundant-compute fallback);
    * axis joins the spatial side from ``src.batch_axes`` ->
      ``batch_to_spatial`` (U-Net decoder ascent);
    * axis joins the spatial side from replication ->
      ``replicated_to_spatial`` (local slice).

    Returns ``(h, sample_ids)`` with ids updated through batch moves.
    """
    for d in range(3):
        a_src, a_dst = src.spatial_axes[d], dst.spatial_axes[d]
        dim = _SPATIAL_DIMS[d]
        if a_src == a_dst:
            continue
        if a_src is not None and a_dst is not None:
            raise ValueError(
                f"unsupported transition: dim {d} moves between spatial "
                f"axes {a_src!r} -> {a_dst!r} (re-partitioning a dim onto "
                "a different axis is not a plan transition)")
        if a_src is not None:
            if a_src in dst.batch_axes and a_src not in src.batch_axes:
                kind = "spatial_to_batch"
                fn = spatial_to_batch_oracle if oracle else spatial_to_batch
                h = fn(h, a_src, dim)
                if sample_ids is not None:
                    sample_ids = shard_batch(sample_ids, (a_src,))
            else:
                kind = "spatial_to_replicated"
                h = spatial_to_replicated(h, a_src, dim)
        else:
            if a_dst in src.batch_axes and a_dst not in dst.batch_axes:
                kind = "batch_to_spatial"
                h = batch_to_spatial(h, a_dst, dim)
                # ids for the re-widened batch would need an all_gather;
                # no current consumer needs them past an ascent.
                sample_ids = None
            else:
                kind = "replicated_to_spatial"
                h = replicated_to_spatial(h, a_dst, dim)
        # §14 trace-time marker: stage-boundary reshards execute inside
        # the jitted program, so the tracer records how many transitions
        # (and which lowering) each traced program emits.
        trace_lib.count("reshard.transitions")
        trace_lib.instant("trace.reshard", dim=d, kind=kind)
    return h, sample_ids


# ------------------------------------------------- cross-group (§13) -----
def group_sharding(mesh: jax.sharding.Mesh,
                   batch_axes: Sequence[str] = ("data",)
                   ) -> jax.sharding.NamedSharding:
    """Batch-sharded ``NamedSharding`` on one pipeline group's mesh: dim 0
    split over the group's data axes, everything else replicated — the
    layout every activation (and micro-batch input) holds inside a
    group."""
    spec = jax.sharding.PartitionSpec(
        tuple(a for a in batch_axes if a in mesh.axis_names) or None)
    return jax.sharding.NamedSharding(mesh, spec)


def cross_group(x: jax.Array,
                dst: jax.sharding.NamedSharding) -> jax.Array:
    """Move a stage-boundary activation (or its cotangent, on the way
    back down) to the next pipeline group's devices.

    Pipeline groups are *disjoint* device sets, so this is not a
    collective inside one mesh: it lowers to point-to-point device
    copies (``jax.device_put`` with a destination sharding). Both groups
    shard only the batch dim, and the per-group data degrees are equal,
    so rank ``j`` of the source group sends its whole shard to rank
    ``j`` of the destination group — the minimal transfer for the
    layout. Asynchronous: dispatch returns immediately, which is what
    lets 1F1B overlap the copy with both groups' compute."""
    trace_lib.count("pipe.cross_group")
    return jax.device_put(x, dst)


def to_group(tree, dst: jax.sharding.NamedSharding):
    """``cross_group`` over a pytree, skipping leaves already placed on
    the destination (a no-op placement costs a dispatch anyway; the
    check keeps steady-state micro-batch loops transfer-only where data
    actually moves)."""
    def put(leaf):
        if getattr(leaf, "sharding", None) == dst:
            return leaf
        return jax.device_put(leaf, dst)
    return jax.tree_util.tree_map(put, tree)


__all__ = [
    "SpatialPartitioning", "spatial_allgather",
    "spatial_to_batch", "batch_to_spatial",
    "spatial_to_replicated", "replicated_to_spatial",
    "spatial_to_batch_oracle", "shard_batch", "apply",
    "group_sharding", "cross_group", "to_group",
]
