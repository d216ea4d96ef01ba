"""jit'd wrapper: builds the transposed im2col patch matrix, a bounded
chunk at a time, and calls the GEMM kernel on each chunk.

Two properties the conv path (``core/spatial_conv.py``) relies on, as in
``kernels/bn_act/ops.py``:

* the interpret-mode decision is made at TRACE time, not import time, so
  a bare import never touches the backend and a backend chosen after the
  import wins;
* the kernel carries a ``custom_vjp`` whose backward is the XLA oracle's
  VJP, so the Pallas forward can sit under ``value_and_grad`` (Pallas
  calls have no transpose rule of their own).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv3d.kernel import conv3d_gemm
from repro.kernels.conv3d.ref import conv3d_valid as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# patch-matrix bytes per GEMM call: the matrix is k^3 times the input
# slab it is cut from, so the forward builds it one (sample, run of output
# depth rows) chunk at a time in a sequential loop, and its HBM footprint
# stays at one chunk whatever the volume
_CHUNK_BYTES = 128 << 20


def _patches_t(xc: jax.Array, k: int, stride: int):
    """(k^3*Cin, Do*Ho*Wo) for one channel-major chunk ``xc`` (Cin, D, H,
    W): row (offset, cin) holds that filter offset's strided view of
    channel ``cin``, voxels in DHW order, so no intermediate carries the
    thin channel dim on the lanes."""
    cin, din, hin, win = xc.shape
    do, ho, wo = ((n - k) // stride + 1 for n in (din, hin, win))
    views = [jax.lax.slice(xc, (0, kd, kh, kw),
                           (cin, kd + (do - 1) * stride + 1,
                            kh + (ho - 1) * stride + 1,
                            kw + (wo - 1) * stride + 1),
                           (1, stride, stride, stride))
             for kd in range(k) for kh in range(k) for kw in range(k)]
    return jnp.stack(views).reshape(k ** 3 * cin, do * ho * wo)


def _depth_chunk(do: int, row_bytes: int) -> int:
    """Output depth rows per chunk: the largest divisor of ``do`` whose
    patch rows fit ``_CHUNK_BYTES`` (at least one row)."""
    fit = max(_CHUNK_BYTES // row_bytes, 1)
    return max(d for d in range(1, min(fit, do) + 1) if do % d == 0)


def _conv_fwd_kernel(x, w, stride):
    k, cout = w.shape[0], w.shape[4]
    n, din, hin, win, cin = x.shape
    do, ho, wo = ((s - k) // stride + 1 for s in (din, hin, win))
    # a patch row pads its W run to whole lanes on the chip
    row_bytes = (k ** 3 * cin * ho * -(-wo // 128) * 128
                 * x.dtype.itemsize)
    dc = _depth_chunk(do, row_bytes)
    nc = do // dc
    xt = jnp.moveaxis(x, -1, 1)                    # (N, Cin, D, H, W)
    wt = w.reshape(-1, cout).T                     # rows match _patches_t
    interpret = _interpret()

    def chunk(i):
        xc = jax.lax.dynamic_slice(
            xt, (i // nc, 0, (i % nc) * dc * stride, 0, 0),
            (1, cin, (dc - 1) * stride + k, hin, win))[0]
        return conv3d_gemm(wt, _patches_t(xc, k, stride),
                           interpret=interpret)

    out_t = jax.lax.map(chunk, jnp.arange(n * nc))  # (N*nc, Cout, dc*Ho*Wo)
    out = out_t.reshape(n, nc, cout, dc, ho, wo)
    return jnp.transpose(out, (0, 1, 3, 4, 5, 2)).reshape(
        n, do, ho, wo, cout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(x, w, stride):
    return _conv_fwd_kernel(x, w, stride)


def _conv_fwd(x, w, stride):
    return _conv_fwd_kernel(x, w, stride), (x, w)


def _conv_bwd(stride, res, g):
    _, vjp = jax.vjp(lambda x, w: _ref(x, w, stride=stride), *res)
    return vjp(g)


_conv.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.jit, static_argnames=("stride",))
def conv3d_valid(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """VALID conv over a pre-padded input. x: (N, Din, H, W, Cin);
    w: (k, k, k, Cin, Cout). Output spatial dim = (Din - k) // stride + 1."""
    return _conv(x, w, stride)
