"""Fused batchnorm-normalize + LeakyReLU (Pallas TPU).

Paper §III-A: "operations that are normally considered cheap can in fact
dominate runtime if not well implemented" — at 512^3 the BN normalize pass
alone is a full HBM round-trip of a multi-GiB activation. Fusing
normalize+activation halves that traffic (the statistics psum stays in
core/dist_norm.py — it is a cross-device reduction). VMEM tiling: rows of
a lane-dense 2-D view of the channel-minor activation. When C divides the
128 lanes, each view row packs 128/C voxels and the per-channel
mean/var/scale/bias vectors are tiled across the lanes to match; otherwise
a row is one voxel's C channels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_ROW_TILE = 1024


def _bn_act_kernel(x_ref, mean_ref, var_ref, scale_ref, bias_ref, out_ref,
                   *, eps: float, slope: float):
    x = x_ref[...]
    inv = jax.lax.rsqrt(var_ref[...] + eps)
    y = (x.astype(jnp.float32) - mean_ref[...]) * (inv * scale_ref[...]) \
        + bias_ref[...]
    if slope != 1.0:
        y = jnp.where(y >= 0, y, slope * y)
    out_ref[...] = y.astype(out_ref.dtype)


def bn_leaky_relu(x, mean, var, scale, bias, *, eps=1e-5,
                  negative_slope=0.01, interpret: bool = False):
    """x: (..., C); per-channel stats (C,)."""
    orig_shape = x.shape
    C = x.shape[-1]
    reps = _LANES // C if (C < _LANES and _LANES % C == 0
                           and x.size % _LANES == 0) else 1
    width = C * reps
    rows = x.size // width
    xf = x.reshape(rows, width)
    row_tile = rows if rows <= _ROW_TILE else _ROW_TILE

    def vec(v):
        return jnp.tile(v.astype(jnp.float32), reps).reshape(1, width)

    kern = functools.partial(_bn_act_kernel, eps=eps, slope=negative_slope)
    row_spec = pl.BlockSpec((row_tile, width), lambda r: (r, 0))
    vec_spec = pl.BlockSpec((1, width), lambda r: (0, 0))
    out = pl.pallas_call(
        kern,
        grid=(pl.cdiv(rows, row_tile),),
        in_specs=[row_spec, vec_spec, vec_spec, vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        interpret=interpret,
    )(xf, vec(mean), vec(var), vec(scale), vec(bias))
    return out.reshape(orig_shape)
