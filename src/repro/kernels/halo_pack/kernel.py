"""Halo pack/unpack Pallas kernels (paper §III-A: "optimized packing/
unpacking kernels for the neighbor communication of boundary regions").

On GPU the paper's cost was strided gathers before NCCL sends; the TPU
analogue is strided HBM->VMEM copies ahead of the collective-permute. The
pack kernel reads only the two boundary slabs of the depth dim and
streams them into contiguous send buffers; unpack fuses the halo concat
into a single padded-buffer write instead of XLA's concatenate (which
would re-copy the body).

Both kernels see the NDHWC tensor as (N, D, H, W*C): a free row-major
view that puts W*C on the lanes (dense even at 4 channels), and tile H
in sublane multiples so a block stays a few MiB whatever the volume.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

_BLOCK_BYTES = 2 << 20


def _h_tile(h: int, row_bytes: int) -> int:
    """Rows of H per block: all of H if it fits the budget, else the
    largest multiple of 8 dividing H that does (at least 8)."""
    if h * row_bytes <= _BLOCK_BYTES or h % 8:
        return h
    t = max(_BLOCK_BYTES // row_bytes // 8, 1) * 8
    while h % t:
        t -= 8
    return t


def _pack_kernel(lead_ref, trail_ref, lo_out_ref, hi_out_ref):
    lo_out_ref[...] = lead_ref[...]
    hi_out_ref[...] = trail_ref[...]


def pack_depth(x: jax.Array, lo: int, hi: int, *, interpret: bool = False):
    """x: (N, D, H, W, C) -> (lo_face (N,hi,H,W,C), hi_face (N,lo,H,W,C)).

    Only the boundary slabs are read: the leading ``hi`` rows and the
    trailing ``lo`` rows of depth, each as one block along D.
    """
    N, D, H, W, C = x.shape
    lead, trail = max(hi, 1), max(lo, 1)
    if D % trail:
        raise ValueError(f"pack_depth: depth {D} is not a multiple of the "
                         f"trailing face width {trail}")
    xv = x.reshape(N, D, H, W * C)
    th = _h_tile(H, W * C * x.dtype.itemsize * max(lead, trail))
    out = pl.pallas_call(
        _pack_kernel,
        grid=(N, H // th),
        in_specs=[
            pl.BlockSpec((1, lead, th, W * C), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((1, trail, th, W * C),
                         lambda n, h: (n, D // trail - 1, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, lead, th, W * C), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((1, trail, th, W * C), lambda n, h: (n, 0, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, lead, H, W * C), x.dtype),
            jax.ShapeDtypeStruct((N, trail, H, W * C), x.dtype),
        ],
        interpret=interpret,
    )(xv, xv)
    lo_face = out[0].reshape(N, lead, H, W, C) if hi else None
    hi_face = out[1].reshape(N, trail, H, W, C) if lo else None
    return lo_face, hi_face


def _unpack_kernel(lo_ref, x_ref, hi_ref, out_ref, *, lo: int, d: int):
    out_ref[:, :lo] = lo_ref[...]
    out_ref[:, lo:lo + d] = x_ref[...]
    out_ref[:, lo + d:] = hi_ref[...]


def unpack_depth(x: jax.Array, lo_buf: jax.Array, hi_buf: jax.Array,
                 *, interpret: bool = False) -> jax.Array:
    """Write [lo_buf | x | hi_buf] along depth into one padded buffer."""
    N, D, H, W, C = x.shape
    lo = lo_buf.shape[1]
    hi = hi_buf.shape[1]
    Dp = D + lo + hi
    wc = W * C
    th = _h_tile(H, wc * x.dtype.itemsize * Dp)

    def spec(d):
        return pl.BlockSpec((1, d, th, wc), lambda n, h: (n, 0, h, 0))

    out = pl.pallas_call(
        functools.partial(_unpack_kernel, lo=lo, d=D),
        grid=(N, H // th),
        in_specs=[spec(lo), spec(D), spec(hi)],
        out_specs=spec(Dp),
        out_shape=jax.ShapeDtypeStruct((N, Dp, H, wc), x.dtype),
        interpret=interpret,
    )(lo_buf.reshape(N, lo, H, wc), x.reshape(N, D, H, wc),
      hi_buf.reshape(N, hi, H, wc))
    return out.reshape(N, Dp, H, W, C)
