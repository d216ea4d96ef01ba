"""Direct 3D convolution as one im2col GEMM (Pallas TPU).

TPU adaptation of the paper's cuDNN 3-D conv (DESIGN.md §2): a k^3
VALID convolution is one (Cout x k^3*Cin) @ (k^3*Cin x voxels) matmul
over the transposed patch matrix, whose rows are the k^3 shifted
(strided) views of the input, built by XLA in ops.py. Folding the filter
offsets into the contraction keeps the thin early layers (Cin = 4 at
CosmoFlow's input) off 4-lane operands, and putting voxels on the lane
axis keeps the operands and the output lane-dense whatever the channel
counts. The kernel is a tiled MXU accumulation over a
(Cout-tile, voxel-tile, K-tile) grid, K innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MAX_K_TILE = 2048
_MAX_VOXEL_TILE = 8192
_BLOCK_BYTES = 2 << 20  # one patch block; double-buffered by Pallas


def _k_tile(k: int) -> int:
    """The contraction tile: the largest lane multiple up to
    ``_MAX_K_TILE`` that divides a large K, else all of K (K is never
    split unevenly: a partial K block would add garbage into the sum)."""
    if k > _MAX_K_TILE:
        for t in range(_MAX_K_TILE, 0, -_LANES):
            if k % t == 0:
                return t
    return k


def _voxel_tile(m: int, k_tile: int, itemsize: int) -> int:
    """Voxels per block: a lane multiple within the block budget, or all
    of them when fewer than one lane row (the last block may be partial:
    its out-of-range columns are never written back)."""
    if m <= _LANES:
        return m
    rows = -(-k_tile // 8) * 8
    t = max(_BLOCK_BYTES // (rows * itemsize) // _LANES, 1) * _LANES
    return min(t, -(-m // _LANES) * _LANES, _MAX_VOXEL_TILE)


def _gemm_kernel(w_ref, p_ref, out_ref, acc_ref, *, upcast: bool):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w, p = w_ref[...], p_ref[...]
    if upcast:  # the CPU interpreter has no bf16 x bf16 -> f32 dot
        w, p = w.astype(jnp.float32), p.astype(jnp.float32)
    acc_ref[...] += jnp.dot(w, p, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def conv3d_gemm(wt: jax.Array, patches_t: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """``wt`` (Cout, K) @ ``patches_t`` (K, M) -> (Cout, M), fp32
    accumulation, in the patches' dtype."""
    cout, k = wt.shape
    m = patches_t.shape[1]
    tk = _k_tile(k)
    tm = _voxel_tile(m, tk, patches_t.dtype.itemsize)
    tc = cout if cout <= 256 else 256
    if cout % tc:
        tc = cout
    grid = (cout // tc, pl.cdiv(m, tm), k // tk)
    upcast = interpret and patches_t.dtype != jnp.float32
    return pl.pallas_call(
        functools.partial(_gemm_kernel, upcast=upcast),
        grid=grid,
        in_specs=[pl.BlockSpec((tc, tk), lambda c, v, j: (c, j)),
                  pl.BlockSpec((tk, tm), lambda c, v, j: (j, v))],
        out_specs=pl.BlockSpec((tc, tm), lambda c, v, j: (c, v)),
        out_shape=jax.ShapeDtypeStruct((cout, m), patches_t.dtype),
        scratch_shapes=[pltpu.VMEM((tc, tm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(wt.astype(patches_t.dtype), patches_t)
