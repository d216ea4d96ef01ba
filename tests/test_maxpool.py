"""``maxpool3d``'s saved-window-index path against autodiff of
``lax.reduce_window`` max, and the cases that keep ``reduce_window``.

Where window == stride and the local widths divide it, the pool saves the
uint8 offset of each window's first maximum and builds its gradient from
it; value and gradient must equal ``jax.vjp`` of ``reduce_window`` (whose
backward is XLA's select-and-scatter) bit for bit, ties included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.spatial_conv import (SpatialPartitioning,
                                     index_pool_applies, maxpool3d)

PART = SpatialPartitioning()


def _reference(x, window=2, stride=2):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1,) + (window,) * 3 + (1,),
                             (1,) + (stride,) * 3 + (1,), "VALID")


def _input(kind, shape, dtype):
    key = jax.random.PRNGKey(0)
    if kind == "random":
        x = jax.random.normal(key, shape)
    elif kind == "equal_windows":
        # every window holds one value eight times: the tie rule decides
        n, d, h, w, c = shape
        x = jax.random.normal(key, (n, d // 2, h // 2, w // 2, c))
        for axis in (1, 2, 3):
            x = jnp.repeat(x, 2, axis)
    elif kind == "zeros":
        x = jnp.zeros(shape)
    else:  # quantised: many ties inside windows, not all
        x = jnp.round(jax.random.normal(key, shape) * 2) / 2
    return x.astype(dtype)


def _primitives(fn, x):
    jaxpr = jax.make_jaxpr(lambda x: jax.vjp(fn, x)[1](fn(x)))(x)
    seen = set()

    def walk(jp):
        for eqn in jp.eqns:
            seen.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return seen


@pytest.mark.parametrize("batch,channels", [(1, 16), (4, 32)])
@pytest.mark.parametrize("kind", ["random", "equal_windows", "zeros",
                                  "quantised"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_index_pool_matches_reduce_window_bitwise(dtype, kind, batch,
                                                  channels):
    shape = (batch, 8, 6, 4, channels)
    x = _input(kind, shape, dtype)
    g = jax.random.normal(jax.random.PRNGKey(1),
                          (batch, 4, 3, 2, channels)).astype(dtype)
    y, vjp = jax.vjp(lambda t: maxpool3d(t, PART), x)
    y_ref, vjp_ref = jax.vjp(_reference, x)
    assert y.dtype == y_ref.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_ref, np.float32))
    (dx,), (dx_ref,) = vjp(g), vjp_ref(g)
    np.testing.assert_array_equal(np.asarray(dx, np.float32),
                                  np.asarray(dx_ref, np.float32))
    # exactly one voxel of each window takes its gradient
    hit = (np.asarray(dx, np.float32) != 0).reshape(
        batch, 4, 2, 3, 2, 2, 2, channels).sum(axis=(2, 4, 6))
    assert hit.max() <= 1


@pytest.mark.parametrize("kind", ["random", "quantised"])
def test_index_pool_window3_matches_reduce_window_bitwise(kind):
    """window = stride = 3: 27 offsets, still one uint8 per window."""
    x = _input(kind, (2, 9, 6, 3, 4), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 2, 1, 4))
    y, vjp = jax.vjp(lambda t: maxpool3d(t, PART, window=3, stride=3), x)
    y_ref, vjp_ref = jax.vjp(lambda t: _reference(t, 3, 3), x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(vjp(g)[0]),
                                  np.asarray(vjp_ref(g)[0]))


def test_index_pool_step_has_no_select_and_scatter():
    x = jnp.ones((2, 8, 8, 8, 16))
    grad = jax.jit(jax.grad(lambda t: maxpool3d(t, PART).sum()))
    prims = _primitives(lambda t: maxpool3d(t, PART), x)
    assert not {"reduce_window_max", "select_and_scatter_add"} & prims, prims
    assert "select-and-scatter" not in grad.lower(x).compile().as_text()


@pytest.mark.parametrize("widths,window,stride,index", [
    ((8, 6, 4), 2, 2, True),
    ((64, 128, 128), 2, 2, True),   # a 4-way depth shard of CosmoFlow-256
    ((9, 8, 8), 2, 2, False),
    ((8, 8, 8), 3, 2, False),
    ((12, 12, 12), 3, 2, False),    # divisible, but windows overlap
    ((8, 8, 8), 2, 1, False),
    ((9, 9, 9), 3, 3, True),
    ((8, 8, 8), 8, 8, False),       # 512 offsets overflow a uint8
])
def test_index_pool_applies(widths, window, stride, index):
    assert index_pool_applies(widths, window, stride) is index


@pytest.mark.parametrize("shape,window,stride", [
    ((2, 12, 12, 12, 4), 3, 2),  # overlapping windows: window != stride
    ((2, 9, 8, 8, 4), 2, 2),   # an odd local depth
], ids=["window_ne_stride", "odd_local_size"])
def test_other_pools_keep_reduce_window(shape, window, stride):
    """The fallback lowers as before: ``reduce_window`` forward, autodiff's
    select-and-scatter backward, and the same values as the reference
    (SAME-style padding where window > stride, VALID otherwise)."""
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    fn = lambda t: maxpool3d(t, PART, window=window, stride=stride)
    prims = _primitives(fn, x)
    assert {"reduce_window_max", "select_and_scatter_add"} <= prims, prims
    pad = max(window - stride, 0)
    lo, hi = pad // 2, pad - pad // 2
    ref = lax.reduce_window(x, -jnp.inf, lax.max, (1,) + (window,) * 3 + (1,),
                            (1,) + (stride,) * 3 + (1,),
                            ((0, 0),) + ((lo, hi),) * 3 + ((0, 0),))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(ref))
