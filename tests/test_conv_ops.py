"""The conv path's primitives at spatial=1 against plain references.

``conv3d``, ``deconv3d``, ``avgpool3d_global`` and ``distributed_batchnorm``
with no partitioned dims and no reduce axes, on the one CPU device. Each
reference is written here in plain ``lax``/``jnp``/``numpy``: SAME padding
by the rule of DESIGN.md §2, the up-convolution as a scatter of each
kernel offset onto its strided sub-grid, batch-norm on batch statistics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import dist_norm
from repro.core.spatial_conv import (SpatialPartitioning, avgpool3d_global,
                                     conv3d, deconv3d)

PART = SpatialPartitioning()
# bf16 keeps 8 mantissa bits: a rounded input, a rounded weight and a
# rounded output each cost up to 2^-9 of their magnitude
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
            * scale).astype(dtype)


def _close(got, want, dtype):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got, want, rtol=TOL[dtype],
                               atol=TOL[dtype] * scale)


# --------------------------------------------------------------- conv3d ----
@pytest.mark.parametrize("n,width,cin,cout,k,stride,dtype", [
    (2, 8, 4, 16, 3, 1, jnp.float32),      # CosmoFlow conv0: 4 -> 16
    (2, 8, 4, 16, 3, 1, jnp.bfloat16),
    (1, 8, 64, 128, 3, 2, jnp.float32),    # CosmoFlow conv3, stride 2
    (1, 8, 64, 128, 3, 2, jnp.bfloat16),
    (1, 7, 3, 5, 3, 1, jnp.float32),       # odd width
    (1, 7, 3, 5, 3, 2, jnp.float32),       # odd width, stride 2
    (1, 9, 2, 4, 3, 2, jnp.bfloat16),
    (2, 6, 8, 3, 1, 1, jnp.float32),       # 1x1x1, the U-Net head
    (1, 6, 4, 8, 5, 1, jnp.float32),       # wider kernel
    (1, 10, 16, 32, 3, 1, jnp.bfloat16),
])
def test_conv3d_matches_same_padded_lax_conv(n, width, cin, cout, k, stride,
                                             dtype):
    x = _rand(0, (n, width, width, width, cin), dtype)
    w = _rand(1, (k, k, k, cin, cout), dtype, scale=(k ** 3 * cin) ** -0.5)
    lo = (k - stride) // 2
    hi = max(k - stride, 0) - lo
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x, w: conv3d(x, w, PART, stride=stride))(x, w)
        want = lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32),
            window_strides=(stride,) * 3, padding=[(lo, hi)] * 3,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    assert got.dtype == dtype
    out = (width + lo + hi - k) // stride + 1
    assert got.shape == (n, out, out, out, cout)
    _close(got, want, dtype)


# ------------------------------------------------------------- deconv3d ----
@pytest.mark.parametrize("cin,cout", [(8, 4), (16, 8), (3, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_deconv3d_matches_strided_scatter(cin, cout, dtype):
    s = 2
    x = _rand(2, (2, 3, 4, 5, cin), dtype)
    w = _rand(3, (s, s, s, cin, cout), dtype, scale=cin ** -0.5)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x, w: deconv3d(x, w, PART, stride=s))(x, w)
    xn = np.asarray(x, np.float64)
    wn = np.asarray(w, np.float64)
    n, d, h, wd, _ = xn.shape
    want = np.zeros((n, s * d, s * h, s * wd, cout))
    for a in range(s):
        for b in range(s):
            for c in range(s):
                # offset (a, b, c) of each output block takes the
                # mirrored kernel tap (a transposed conv's kernel flip)
                want[:, a::s, b::s, c::s, :] = np.einsum(
                    "ndhwi,io->ndhwo", xn,
                    wn[s - 1 - a, s - 1 - b, s - 1 - c])
    assert got.dtype == dtype
    _close(got, want, dtype)


# ----------------------------------------------------- avgpool3d_global ----
@pytest.mark.parametrize("shape", [(2, 4, 4, 4, 8), (3, 2, 6, 5, 3)])
def test_avgpool3d_global_matches_mean(shape):
    x = _rand(4, shape, jnp.float32)
    got = jax.jit(lambda x: avgpool3d_global(x, PART))(x)
    want = jnp.mean(x, axis=(1, 2, 3))
    assert got.shape == (shape[0], shape[-1])
    _close(got, want, jnp.float32)


# ------------------------------------------------ distributed_batchnorm ----
@pytest.mark.parametrize("slope,dtype", [
    (None, jnp.float32), (0.0, jnp.float32), (0.01, jnp.float32),
    (0.01, jnp.bfloat16),
])
def test_batchnorm_matches_batch_statistics(slope, dtype):
    c, eps = 6, 1e-5
    x = _rand(5, (3, 4, 5, 2, c), dtype, scale=2.0) + jnp.asarray(
        0.5, dtype)
    scale = _rand(6, (c,), dtype)
    bias = _rand(7, (c,), dtype)
    got = jax.jit(lambda x, s, b: dist_norm.distributed_batchnorm(
        x, s, b, (), eps=eps, activation_slope=slope))(x, scale, bias)
    xn = np.asarray(x, np.float64)
    mean = xn.mean(axis=(0, 1, 2, 3))
    var = xn.var(axis=(0, 1, 2, 3))
    want = ((xn - mean) / np.sqrt(var + eps) * np.asarray(scale, np.float64)
            + np.asarray(bias, np.float64))
    if slope is not None:
        want = np.where(want >= 0, want, slope * want)
    assert got.dtype == dtype
    _close(got, want, dtype)
