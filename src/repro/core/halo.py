"""Halo exchange for spatially-partitioned tensors (paper §III-A).

All functions here run *inside* ``shard_map``: they see the local shard
of a spatially-partitioned activation tensor and exchange boundary slabs
with neighbouring shards along a named mesh axis via ``jax.lax.ppermute``
(which lowers to ``collective-permute`` on TPU ICI — the analogue of the
paper's P2P NVLink/InfiniBand sends).

Two styles are exposed (DESIGN.md §3):

* ``halo_exchange`` — the legacy *blocking* exchange: two ``ppermute``s,
  then the halos are concatenated onto the local block before any compute.
  Kept as the reference oracle for the overlapped path.
* ``start_halo_exchange`` / ``unpack_halo`` — the *packed* exchange behind
  the interior/boundary-decomposed conv (``core/spatial_conv.py``). The
  send slabs for both faces are extracted in one pass (optionally by the
  ``kernels/halo_pack`` Pallas kernel) and the collectives are issued
  before any compute that depends on them, so XLA's latency-hiding
  scheduler can overlap them with the interior convolution. The number of
  ``ppermute``s emitted is the information-theoretic minimum: a shard
  needs data originating at *both* neighbours while one ``ppermute``
  delivers each shard data from exactly one source, so a bidirectional
  halo costs one ``ppermute`` per direction — except on a 2-way axis,
  where both neighbours are the same device and a single swap ``ppermute``
  carrying the packed [lo-face | hi-face] buffer covers both directions.

Conventions
-----------
* A spatial dimension of the *global* tensor is partitioned contiguously
  over a mesh axis: shard ``i`` owns ``[i*W_loc, (i+1)*W_loc)``.
* ``ppermute`` leaves zeros in unpaired destinations, which is exactly the
  zero-padding needed at the global boundary for SAME convolutions, so the
  global-boundary case needs no special handling.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compat
from repro.obs import trace as trace_lib


def _send(x: jax.Array, axis_name: str, perm) -> jax.Array:
    """One halo ``ppermute``, under the ``halo`` scope that names it in a
    device trace (one-shard boundary zeros are the conv's padding, not an
    exchange, and stay outside it)."""
    with jax.named_scope("halo"):
        return lax.ppermute(x, axis_name, perm)


def _shift_perm(n: int, direction: int):
    """Pairs (src, dst) shifting data by ``direction`` (+1: to next rank)."""
    if direction > 0:
        return [(i, i + 1) for i in range(n - 1)]
    return [(i + 1, i) for i in range(n - 1)]


def halo_exchange(
    x: jax.Array,
    axis_name: str,
    dim: int,
    lo: int,
    hi: int,
    wrap: bool = False,
) -> jax.Array:
    """Pad local shard ``x`` along ``dim`` with neighbour boundary slabs.

    ``lo`` rows are received from the previous rank (its trailing slab) and
    ``hi`` rows from the next rank (its leading slab). Returns the padded
    local block of size ``W_loc + lo + hi`` along ``dim``. Ranks at the
    global boundary receive zeros (SAME-conv semantics) unless ``wrap``.
    """
    if lo == 0 and hi == 0:
        return x
    n = compat.axis_size(axis_name)
    parts = []
    if lo > 0:
        if n == 1:
            recv_lo = (
                lax.slice_in_dim(x, x.shape[dim] - lo, x.shape[dim], axis=dim)
                if wrap else jnp.zeros_like(lax.slice_in_dim(x, 0, lo, axis=dim))
            )
        else:
            send = lax.slice_in_dim(x, x.shape[dim] - lo, x.shape[dim], axis=dim)
            perm = _shift_perm(n, +1)
            if wrap:
                perm = perm + [(n - 1, 0)]
            recv_lo = _send(send, axis_name, perm)
        parts.append(recv_lo)
    parts.append(x)
    if hi > 0:
        if n == 1:
            recv_hi = (
                lax.slice_in_dim(x, 0, hi, axis=dim)
                if wrap else jnp.zeros_like(lax.slice_in_dim(x, 0, hi, axis=dim))
            )
        else:
            send = lax.slice_in_dim(x, 0, hi, axis=dim)
            perm = _shift_perm(n, -1)
            if wrap:
                perm = perm + [(0, n - 1)]
            recv_hi = _send(send, axis_name, perm)
        parts.append(recv_hi)
    return jnp.concatenate(parts, axis=dim)


class HaloSlabs(NamedTuple):
    """Received boundary slabs along one dim: ``lo`` came from the previous
    rank (width = halo lo), ``hi`` from the next rank (width = halo hi).
    ``None`` means that side needs no halo. Global-boundary shards hold
    zeros (SAME-conv semantics) unless the exchange wrapped."""

    lo: Optional[jax.Array]
    hi: Optional[jax.Array]


def _extract_faces(x: jax.Array, dim: int, lo: int, hi: int,
                   use_pallas: bool = False):
    """Send slabs (to_next, to_prev): the trailing ``lo`` rows go to the
    next rank (becoming its lo halo) and the leading ``hi`` rows to the
    previous rank. With ``use_pallas`` (depth dim of an NDHWC tensor) both
    faces stream out of one fused pass over the boundary region."""
    if use_pallas and dim == 1 and x.ndim == 5:
        from repro.kernels.halo_pack import ops as pack_ops

        lo_face, hi_face = pack_ops.pack(x, lo, hi)
        return hi_face, lo_face  # hi_face = trailing lo rows, and vice versa
    to_next = (lax.slice_in_dim(x, x.shape[dim] - lo, x.shape[dim], axis=dim)
               if lo else None)
    to_prev = lax.slice_in_dim(x, 0, hi, axis=dim) if hi else None
    return to_next, to_prev


def start_halo_exchange(
    x: jax.Array,
    axis_name: str,
    dim: int,
    lo: int,
    hi: int,
    wrap: bool = False,
    use_pallas: bool = False,
) -> HaloSlabs:
    """Issue the halo sends for ``x`` along ``dim`` and return the received
    slabs WITHOUT stitching them onto the local block.

    This is the comm half of the interior/boundary decomposition: callers
    trace it *first*, compute interior work that does not depend on the
    results, and only then consume the slabs — giving the compiler's
    scheduler the freedom to overlap the collective with the interior
    compute (paper §III-C: ``FP = max{Comp(D_main), halo} + Comp(D_halo)``).

    Emits the minimum number of ``ppermute``s: zero when no halo is
    needed, ONE on a 2-way axis (both faces packed into a single
    contiguous buffer and swapped with the only neighbour), otherwise one
    per direction.
    """
    if lo == 0 and hi == 0:
        return HaloSlabs(None, None)
    n = compat.axis_size(axis_name)
    # §14 trace-time markers: exchanges execute inside shard_map, so the
    # tracer counts the collectives each traced program EMITS (the
    # minimum-ppermute contract below) rather than timing them — the
    # halo wall cost is the perf model's / fwd probe's to attribute.
    trace_lib.count("halo.exchanges")

    def _zeros(width: int) -> jax.Array:
        shape = x.shape[:dim] + (width,) + x.shape[dim + 1:]
        return jnp.zeros(shape, x.dtype)

    if n == 1:
        to_next, to_prev = _extract_faces(x, dim, lo, hi, use_pallas)
        recv_lo = (to_next if wrap else _zeros(lo)) if lo else None
        recv_hi = (to_prev if wrap else _zeros(hi)) if hi else None
        return HaloSlabs(recv_lo, recv_hi)

    if n == 2:
        # Both neighbours are the same peer: pack [to_next | to_prev] into
        # one contiguous buffer and issue a single swap ppermute.
        to_next, to_prev = _extract_faces(x, dim, lo, hi, use_pallas)
        parts = [p for p in (to_next, to_prev) if p is not None]
        packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts, dim)
        trace_lib.count("halo.ppermutes")
        recv = _send(packed, axis_name, [(0, 1), (1, 0)])
        # recv = [peer trailing lo rows | peer leading hi rows]
        recv_lo = lax.slice_in_dim(recv, 0, lo, axis=dim) if lo else None
        recv_hi = (lax.slice_in_dim(recv, recv.shape[dim] - hi,
                                    recv.shape[dim], axis=dim) if hi else None)
        if not wrap:
            # Only rank 1 has a previous rank and only rank 0 a next rank;
            # the other side sits on the global boundary -> zeros.
            idx = lax.axis_index(axis_name)
            if recv_lo is not None:
                recv_lo = jnp.where(idx == 1, recv_lo, jnp.zeros_like(recv_lo))
            if recv_hi is not None:
                recv_hi = jnp.where(idx == 0, recv_hi, jnp.zeros_like(recv_hi))
        return HaloSlabs(recv_lo, recv_hi)

    to_next, to_prev = _extract_faces(x, dim, lo, hi, use_pallas)
    recv_lo = recv_hi = None
    if lo > 0:
        perm = _shift_perm(n, +1)
        if wrap:
            perm = perm + [(n - 1, 0)]
        trace_lib.count("halo.ppermutes")
        recv_lo = _send(to_next, axis_name, perm)
    if hi > 0:
        perm = _shift_perm(n, -1)
        if wrap:
            perm = perm + [(0, n - 1)]
        trace_lib.count("halo.ppermutes")
        recv_hi = _send(to_prev, axis_name, perm)
    return HaloSlabs(recv_lo, recv_hi)


def unpack_halo(x: jax.Array, slabs: HaloSlabs, dim: int,
                use_pallas: bool = False) -> jax.Array:
    """Stitch received slabs around the local block: [lo | x | hi].

    The Pallas unpack kernel fuses the two concats into one padded-buffer
    write for the depth dim of NDHWC tensors."""
    if slabs.lo is None and slabs.hi is None:
        return x
    if (use_pallas and dim == 1 and x.ndim == 5
            and slabs.lo is not None and slabs.hi is not None):
        from repro.kernels.halo_pack import ops as pack_ops

        return pack_ops.unpack(x, slabs.lo, slabs.hi)
    parts = [p for p in (slabs.lo, x, slabs.hi) if p is not None]
    return jnp.concatenate(parts, axis=dim)


def conv_halo_widths(kernel: int, stride: int) -> Tuple[int, int]:
    """Halo widths (lo, hi) for a SAME conv with ``kernel``/``stride``.

    Assumes the global width and every local shard width are divisible by
    ``stride``. Matches XLA SAME padding: total = kernel - stride (k >= s),
    lo = total // 2, hi = total - lo.
    """
    total = max(kernel - stride, 0)
    lo = total // 2
    return lo, total - lo


def all_gather_dim(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """All-gather shards along ``dim`` (the degenerate 'halo = whole domain'
    case: the replicated reshard and the spatial all-gather)."""
    if compat.axis_size(axis_name) == 1:
        return x
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)
