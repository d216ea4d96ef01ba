"""Spatially-distributed 3D convolution / pooling / deconvolution.

The paper's hybrid-parallel 3D CNN primitive: activations are laid out
NDHWC with the **depth** dimension (optionally also H, W) partitioned over
named mesh axes. Each op is written in "local shard + explicit halo
exchange" style and is meant to be called inside ``shard_map``.

``conv3d`` has two lowerings, selected by the ``overlap_halo`` flag
(``core/flags.py``) or per-call via ``overlap=``:

* blocking (the reference oracle): exchange halos, concatenate them onto
  the local block, run one conv — every MXU cycle waits on the collective.
* overlapped (default, DESIGN.md §3): split the local output into an
  *interior* region whose input windows live entirely on this shard and
  thin *boundary* slabs that need remote rows. The packed halo sends are
  issued first, the interior conv is traced next with **no data
  dependence** on the collective, and the boundary convs + output stitch
  come last — the structure the paper's perf model assumes:
  ``FP_l = max{Comp_l(D_main), Σ_d 2·SR(D_halo_d)} + Comp_l(D_halo)``.

Both lowerings compute each output row from the identical input window, so
they agree to float-accumulation order (tests pin ≤1e-5).

Layout: NDHWC (channel-minor — TPU-friendly; contrast with the paper's
cuDNN NCDHW). The partitioned dims are identified by mesh-axis names in a
``SpatialPartitioning`` descriptor.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compat
from repro.core import flags
from repro.core import halo as halo_lib

# Dimension indices in NDHWC.
_SPATIAL_DIMS = (1, 2, 3)


@dataclasses.dataclass(frozen=True)
class SpatialPartitioning:
    """Which mesh axes shard the D/H/W dims of NDHWC activations.

    ``axes[d]`` is the mesh-axis name sharding spatial dim ``d`` (0=D, 1=H,
    2=W) or None if that dim is unpartitioned. The paper's "8-way depth"
    configuration is ``SpatialPartitioning(('model', None, None))``.

    This is the layout of ONE plan stage: a ``core.plan.ParallelPlan``
    assigns a partitioning per layer range (``Stage.part``) and
    ``core/reshard.py`` moves activations between them, so a network is
    no longer restricted to a single network-wide instance of this.
    """

    axes: Tuple[Optional[str], Optional[str], Optional[str]] = (None, None, None)

    @property
    def active(self) -> Sequence[Tuple[int, str]]:
        return [(d, a) for d, a in enumerate(self.axes) if a is not None]

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axes if a is not None)


def _conv_piece(x: jax.Array, w: jax.Array, stride: int,
                pads: Sequence[Tuple[int, int]],
                use_pallas: bool) -> jax.Array:
    """One local VALID-after-padding conv call (XLA or the Pallas kernel)."""
    if use_pallas:
        from repro.kernels.conv3d import ops as conv_ops

        return conv_ops.conv3d_valid(
            jnp.pad(x, ((0, 0),) + tuple((p, q) for p, q in pads) + ((0, 0),)),
            w,
            stride=stride,
        )
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride,) * 3,
        padding=list(pads),
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )


def _conv3d_blocking(x, w, part, stride, use_pallas):
    """Reference oracle: exchange-concat-then-conv (fully serialized)."""
    k = w.shape[0]
    lo, hi = halo_lib.conv_halo_widths(k, stride)
    pads = []
    for d in range(3):
        axis = part.axes[d]
        if axis is None:
            pads.append((lo, hi))  # plain zero padding, unsharded dim
        else:
            x = halo_lib.halo_exchange(x, axis, _SPATIAL_DIMS[d], lo, hi)
            pads.append((0, 0))
    return _conv_piece(x, w, stride, pads, use_pallas)


def _conv3d_overlap(x, w, part, stride, use_pallas):
    """Interior/boundary decomposition with packed halo exchange.

    The last partitioned dim is decomposed: its halo sends are issued
    first, the interior conv (no remote data) is traced before the slabs
    are consumed, and the two boundary convs + a concat stitch the output.
    Any *earlier* partitioned dims are exchanged up front (packed, minimal
    ppermutes) and concatenated, so the decomposed dim's boundary slabs
    carry the corner halos they need — the paper's configs partition depth
    only, where the single exchange is fully overlapped.
    """
    k = w.shape[0]
    s = stride
    lo, hi = halo_lib.conv_halo_widths(k, s)
    active = list(part.active)
    pads: List[Tuple[int, int]] = [
        (0, 0) if part.axes[d] is not None else (lo, hi) for d in range(3)]

    for d, axis in active[:-1]:
        slabs = halo_lib.start_halo_exchange(
            x, axis, _SPATIAL_DIMS[d], lo, hi, use_pallas=use_pallas)
        x = halo_lib.unpack_halo(x, slabs, _SPATIAL_DIMS[d],
                                 use_pallas=use_pallas)

    d, axis = active[-1]
    dim = _SPATIAL_DIMS[d]
    # Comm first: nothing below depends on `slabs` until the boundary convs.
    slabs = halo_lib.start_halo_exchange(x, axis, dim, lo, hi,
                                         use_pallas=use_pallas)

    D = x.shape[dim]
    n_out = (D + lo + hi - k) // s + 1
    n_lo = -(-lo // s)                       # outputs needing the lo slab
    n_hi = n_out - 1 - (D - k + lo) // s     # outputs needing the hi slab
    if n_lo + n_hi >= n_out:
        # Local width too small to hold an interior region (deep layers of
        # an over-decomposed model): fall back to one conv over the stitched
        # block — the packed exchange above still minimizes the ppermutes.
        return _conv_piece(halo_lib.unpack_halo(x, slabs, dim,
                                                use_pallas=use_pallas),
                           w, s, pads, use_pallas)

    # Interior: windows [o*s - lo, o*s - lo + k) for o in [n_lo, n_out-n_hi)
    # lie entirely inside the local block.
    int_lo = n_lo * s - lo
    int_hi = (n_out - n_hi - 1) * s - lo + k
    out_int = _conv_piece(lax.slice_in_dim(x, int_lo, int_hi, axis=dim),
                          w, s, pads, use_pallas)

    outs = []
    if n_lo > 0:
        x_lo = jnp.concatenate(
            [slabs.lo,
             lax.slice_in_dim(x, 0, (n_lo - 1) * s - lo + k, axis=dim)],
            axis=dim)
        outs.append(_conv_piece(x_lo, w, s, pads, use_pallas))
    outs.append(out_int)
    if n_hi > 0:
        x_hi = jnp.concatenate(
            [lax.slice_in_dim(x, (n_out - n_hi) * s - lo, D, axis=dim),
             slabs.hi],
            axis=dim)
        outs.append(_conv_piece(x_hi, w, s, pads, use_pallas))
    return jnp.concatenate(outs, axis=dim) if len(outs) > 1 else outs[0]


def conv3d(
    x: jax.Array,
    w: jax.Array,
    part: SpatialPartitioning,
    stride: int = 1,
    use_pallas: bool = False,
    overlap: Optional[bool] = None,
) -> jax.Array:
    """SAME-padded distributed 3D conv. x: (N, D, H, W, Cin) local shard;
    w: (k, k, k, Cin, Cout) replicated.

    ``overlap=None`` reads the process-wide ``overlap_halo`` flag;
    ``True``/``False`` force the overlapped or blocking lowering.
    """
    if overlap is None:
        overlap = flags.get("overlap_halo")
    k = w.shape[0]
    lo, hi = halo_lib.conv_halo_widths(k, stride)
    if not overlap or not part.active or (lo == 0 and hi == 0):
        return _conv3d_blocking(x, w, part, stride, use_pallas)
    if all(compat.axis_size(a) == 1 for _, a in part.active):
        # Degenerate meshes (1-way axes) have no collective to hide: the
        # 3-conv decomposition would be pure dispatch overhead.
        return _conv3d_blocking(x, w, part, stride, use_pallas)
    return _conv3d_overlap(x, w, part, stride, use_pallas)


def deconv3d(
    x: jax.Array,
    w: jax.Array,
    part: SpatialPartitioning,
    stride: int = 2,
) -> jax.Array:
    """Transposed conv (U-Net up-convolution). With kernel == stride the
    voxel->block mapping has no overlap, so it is *purely local* under
    spatial partitioning — no halo needed (noted in DESIGN.md)."""
    k = w.shape[0]
    assert k == stride, "distributed deconv implemented for kernel == stride"
    return lax.conv_transpose(
        x,
        w,
        strides=(stride,) * 3,
        padding="VALID",
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )


def index_pool_applies(widths: Sequence[int], window: int,
                       stride: int) -> bool:
    """Whether ``maxpool3d`` lowers through the saved window index for
    local D/H/W ``widths``: windows that tile each local dim exactly, with
    an offset that fits a uint8."""
    return window == stride and window ** 3 <= 256 and all(
        n % window == 0 for n in widths)


def _window_slices(x: jax.Array, window: int) -> Iterator[jax.Array]:
    """The window^3 strided sub-volumes of ``x``, one per offset in
    row-major (d, h, w) window order."""
    n, d, h, w, c = x.shape
    for a, b, e in itertools.product(range(window), repeat=3):
        yield lax.slice(x, (0, a, b, e, 0), (n, d, h, w, c),
                        (1, window, window, window, 1))


def _pool_with_index(x: jax.Array, window: int):
    """Non-overlapping max pool in one pass over the strided sub-volumes:
    the max, bit for bit ``reduce_window``'s, and the uint8 offset of the
    FIRST maximum of each window, select-and-scatter's ``ge`` rule."""
    y = index = None
    for k, sub in enumerate(_window_slices(x, window)):
        if y is None:
            y, index = sub, jnp.zeros(sub.shape, jnp.uint8)
        else:
            index = jnp.where(sub > y, jnp.uint8(k), index)
            y = lax.max(y, sub)
    return y, index


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _maxpool_index(x: jax.Array, window: int) -> jax.Array:
    return _pool_with_index(x, window)[0]


def _maxpool_index_bwd(window, index, g):
    """Each window's gradient lands on its saved offset: ``g`` and the
    index repeated over the window, compared with a window iota. No
    scatter, and no copy of ``x`` kept for it. H is repeated first: where
    XLA puts H in the TPU's lanes, as in CosmoFlow's step, that order
    costs one small relayout instead of three at full size. The ops carry
    the caller's name stack (``transpose(jvp(...))/pool/``)."""
    def spread(t):
        for axis in (2, 3, 1):
            t = jnp.repeat(t, window, axis)
        return t

    dx = spread(g)
    offset = sum((jnp.arange(dx.shape[dim]) % window * window ** (3 - dim))
                 .astype(jnp.uint8).reshape((-1,) + (1,) * (4 - dim))
                 for dim in _SPATIAL_DIMS)
    dx = jnp.where(spread(index) == offset, dx, jnp.zeros((), g.dtype))
    return (dx,)


_maxpool_index.defvjp(_pool_with_index, _maxpool_index_bwd)


def maxpool3d(
    x: jax.Array,
    part: SpatialPartitioning,
    window: int = 2,
    stride: int = 2,
    overlap: Optional[bool] = None,
) -> jax.Array:
    """Distributed max pooling. For window == stride (the paper's pooling)
    no halo is required when local widths divide the stride; the pool then
    saves each window's argmax as a uint8 index and routes the gradient
    through it (DESIGN.md §2) rather than autodiff's select-and-scatter.
    Any other case (window != stride, or local widths the stride does not
    divide) lowers to ``reduce_window``. When a halo IS needed, the
    ``overlap_halo`` flag selects the packed exchange (minimal ppermutes)
    over the legacy blocking one; pooling is too cheap to be worth an
    interior/boundary split."""
    if index_pool_applies([x.shape[d] for d in _SPATIAL_DIMS], window,
                          stride):
        return _maxpool_index(x, window)
    if overlap is None:
        overlap = flags.get("overlap_halo")
    lo, hi = halo_lib.conv_halo_widths(window, stride)
    pads = []
    for d in range(3):
        axis = part.axes[d]
        if axis is None or (lo == 0 and hi == 0):
            pads.append((lo, hi))
        elif overlap:
            slabs = halo_lib.start_halo_exchange(
                x, axis, _SPATIAL_DIMS[d], lo, hi)
            x = halo_lib.unpack_halo(x, slabs, _SPATIAL_DIMS[d])
            pads.append((0, 0))
        else:
            x = halo_lib.halo_exchange(x, axis, _SPATIAL_DIMS[d], lo, hi)
            pads.append((0, 0))
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, window, window, window, 1),
        window_strides=(1, stride, stride, stride, 1),
        padding=((0, 0),) + tuple(pads) + ((0, 0),),
    )


def avgpool3d_global(x: jax.Array, part: SpatialPartitioning) -> jax.Array:
    """Global average pool over (possibly partitioned) spatial dims."""
    local = jnp.mean(x, axis=_SPATIAL_DIMS)
    for _, axis in part.active:
        local = lax.pmean(local, axis)
    return local


def spatial_allgather(x: jax.Array, part: SpatialPartitioning) -> jax.Array:
    """Gather a spatially-partitioned activation to a full local copy.

    The legacy CNN->FC transition (paper: the FC layers are tiny and run
    data-parallel; activations there are a few thousand elements) and the
    equivalence oracle for the plan-driven ``all_to_all`` reshards of
    ``core/reshard.py`` (DESIGN.md §5), which replace it wherever the
    cost model justifies a layout change."""
    for d, axis in part.active:
        x = halo_lib.all_gather_dim(x, axis, _SPATIAL_DIMS[d])
    return x
