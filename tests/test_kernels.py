"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("shape,k,cout,stride", [
    ((2, 10, 10, 10, 3), 3, 8, 1),
    ((1, 9, 9, 9, 4), 3, 16, 2),
    ((2, 12, 8, 8, 8), 5, 4, 1),
    ((1, 6, 6, 6, 2), 1, 8, 1),
    ((1, 7, 7, 7, 16), 3, 32, 1),
    ((2, 22, 22, 22, 1), 3, 4, 1),   # two voxel blocks, the last partial
    ((1, 4, 4, 4, 128), 3, 8, 1),    # K = 3456: three K blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv3d_kernel(shape, k, cout, stride, dtype):
    from repro.kernels.conv3d import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (k, k, k, shape[-1], cout), dtype) * 0.1
    got = ops.conv3d_valid(x, w, stride=stride)
    want = ref.conv3d_valid(x, w, stride=stride)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv3d_kernel_depth_chunks(monkeypatch, stride):
    """A patch budget of one output depth row at stride 1 (two at stride
    2) splits every sample into several chunks."""
    from repro.kernels.conv3d import ops, ref
    # one row: 27 offsets x 2 channels x 4 H rows x a 128-lane W run, fp32
    monkeypatch.setattr(ops, "_CHUNK_BYTES", 27 * 2 * 4 * 128 * 4)
    for shape in ((2, 14, 6, 6, 2), (1, 9, 5, 5, 2)):
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 2, 8)) * 0.1
        got = ops._conv_fwd_kernel(x, w, stride)
        want = ref.conv3d_valid(x, w, stride=stride)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_conv3d_kernel_grad():
    """The Pallas forward's custom VJP is the XLA conv's VJP."""
    from repro.kernels.conv3d import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 6, 6, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 3, 5)) * 0.1
    ct = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 2, 2, 5))

    def loss(f, x, w):
        return jnp.sum(f(x, w, stride=2) * ct)

    got = jax.grad(lambda x, w: loss(ops.conv3d_valid, x, w), (0, 1))(x, w)
    want = jax.grad(lambda x, w: loss(ref.conv3d_valid, x, w), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


def test_halo_pack_unpack_grad():
    """pack/unpack differentiate like the slices and concat they fuse."""
    from repro.kernels.halo_pack import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8, 4, 3))
    lo_buf = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 8, 4, 3))
    hi_buf = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 8, 4, 3))

    def packed(pack, x):
        lo_f, hi_f = pack(x)
        return jnp.sum(lo_f * 2.0) + jnp.sum(hi_f * 3.0)

    got = jax.grad(lambda x: packed(lambda t: ops.pack(t, 1, 2), x))(x)
    want = jax.grad(lambda x: packed(lambda t: ref.pack(t, 1, 1, 2), x))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 8, 4, 3))
    got = jax.grad(lambda *a: jnp.sum(ops.unpack(*a) * w),
                   (0, 1, 2))(x, lo_buf, hi_buf)
    want = jax.grad(lambda *a: jnp.sum(ref.unpack(*a, 1) * w),
                    (0, 1, 2))(x, lo_buf, hi_buf)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("shape,lo,hi", [
    ((2, 8, 4, 4, 3), 1, 1), ((1, 6, 8, 4, 2), 2, 1), ((2, 5, 3, 3, 4), 1, 2),
])
def test_halo_pack_unpack(shape, lo, hi):
    from repro.kernels.halo_pack import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    lo_f, hi_f = ops.pack(x, lo, hi)
    rlo, rhi = ref.pack(x, 1, lo, hi)
    np.testing.assert_allclose(np.asarray(lo_f), np.asarray(rlo))
    np.testing.assert_allclose(np.asarray(hi_f), np.asarray(rhi))
    lo_buf = jax.random.normal(jax.random.PRNGKey(1),
                               shape[:1] + (lo,) + shape[2:])
    hi_buf = jax.random.normal(jax.random.PRNGKey(2),
                               shape[:1] + (hi,) + shape[2:])
    up = ops.unpack(x, lo_buf, hi_buf)
    rup = ref.unpack(x, lo_buf, hi_buf, 1)
    np.testing.assert_allclose(np.asarray(up), np.asarray(rup))


@pytest.mark.parametrize("shape,c", [((2, 5, 5, 5, 16), 16),
                                     ((4, 7, 3, 3, 32), 32),
                                     ((1, 128, 8), 8),
                                     ((5, 20, 20, 20, 8), 8)])
@pytest.mark.parametrize("slope", [0.01, 1.0])
def test_bn_act_kernel(shape, c, slope):
    from repro.kernels.bn_act import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], shape)
    mean = jax.random.normal(ks[1], (c,))
    var = jax.nn.softplus(jax.random.normal(ks[2], (c,)))
    scale = jax.random.normal(ks[3], (c,))
    bias = jax.random.normal(ks[4], (c,))
    got = ops.bn_leaky_relu(x, mean, var, scale, bias, negative_slope=slope)
    want = ref.bn_leaky_relu(x, mean, var, scale, bias,
                             negative_slope=slope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
