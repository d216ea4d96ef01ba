"""The paper's models on one CPU device: the 3D U-Net against a plain U-Net
written here, every config's parameter count against its initialised
params, and each smoke model's forward in train and eval mode.

The plain U-Net uses only ``lax``/``jnp`` and the params the model was
initialised with: SAME 3^3 convs, batch-norm on one-pass batch statistics
+ ReLU, 2^3 max pool, the 2^3 stride-2 up-convolution as a scatter of the
mirrored kernel taps onto the strided sub-grid, skip concat (skip first),
a 1^3 head and the per-voxel softmax cross-entropy.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs
from repro.models import cosmoflow, unet3d

EPS = 1e-5


# ----------------------------------------------------- plain 3D U-Net ----
def _conv(h, w):
    k = w.shape[0]
    return lax.conv_general_dilated(
        h, w, (1, 1, 1), [((k - 1) // 2, k // 2)] * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


def _bn_relu(h, scale, bias):
    # one-pass statistics, E[h^2] - E[h]^2: the (count, sum, sumsq) triple
    # a spatially partitioned batch-norm psums (paper §III-A). Its fp32
    # gradients differ from a two-pass variance's by up to 2% of a leaf's
    # largest entry at width 32, so the reference keeps the same formula
    # and the same order of operations.
    mean = jnp.mean(h, axis=(0, 1, 2, 3))
    var = jnp.maximum(jnp.mean(jnp.square(h), axis=(0, 1, 2, 3))
                      - jnp.square(mean), 0.0)
    return jax.nn.relu((h - mean) * (lax.rsqrt(var + EPS) * scale) + bias)


def _pool(h):
    return lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 2, 1),
                             (1, 2, 2, 2, 1), "VALID")


def _upconv(h, w):
    n, d, hh, ww, _ = h.shape
    # taps[a, b, c] lands on output offset (1-a, 1-b, 1-c) of each block
    y = jnp.einsum("ndhwi,abcio->ndahbwco", h, w[::-1, ::-1, ::-1])
    return y.reshape(n, 2 * d, 2 * hh, 2 * ww, w.shape[-1])


def _plain_unet(p, x, depth):
    def pair(h, pre):
        h = _bn_relu(_conv(h, p[pre + "w0"]), p[pre + "s0"], p[pre + "b0"])
        return _bn_relu(_conv(h, p[pre + "w1"]), p[pre + "s1"], p[pre + "b1"])

    h, skips = x, []
    for lvl in range(depth):
        h = pair(h, f"enc{lvl}_")
        skips.append(h)
        h = _pool(h)
    h = pair(h, "mid_")
    for lvl in reversed(range(depth)):
        h = _upconv(h, p[f"dec{lvl}_up"])
        h = pair(jnp.concatenate([skips[lvl], h], axis=-1), f"dec{lvl}_")
    return jnp.einsum("ndhwi,io->ndhwo", h, p["head_w"][0, 0, 0])


def _plain_loss(p, x, labels, depth):
    logp = jax.nn.log_softmax(_plain_unet(p, x, depth), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@pytest.mark.parametrize("width", [16, 32])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("batch", [1, 2])
def test_unet_matches_plain_unet(width, depth, batch):
    cfg = dataclasses.replace(configs.get_smoke_config("unet3d-256"),
                              input_width=width, depth=depth,
                              base_channels=4)
    params = unet3d.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch, width, width, width, cfg.in_channels))
    labels = jax.random.randint(jax.random.PRNGKey(2),
                                (batch, width, width, width), 0, cfg.out_dim)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, x: unet3d.forward(p, x, cfg))(params, x)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: unet3d.segmentation_loss(p, x, labels, cfg)))(params)
        want_logits = jax.jit(_plain_unet, static_argnums=2)(params, x,
                                                             depth)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: _plain_loss(p, x, labels, depth)))(params)
    assert logits.shape == (batch, width, width, width, cfg.out_dim)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert set(grads) == set(want_grads)
    for k in want_grads:
        g, want = np.asarray(grads[k]), np.asarray(want_grads[k])
        scale = max(float(np.abs(want).max()), 1e-6)
        np.testing.assert_allclose(g / scale, want / scale, rtol=0,
                                   atol=1e-4, err_msg=k)


# ------------------------------------------------------ param counts ----
def _init_fn(cfg):
    return cosmoflow.init_params if cfg.arch == "cosmoflow" else \
        unet3d.init_params


@pytest.mark.parametrize("arch,smoke", [
    ("cosmoflow-128", False), ("cosmoflow-256", False),
    ("cosmoflow-512", False), ("unet3d-256", False),
    ("cosmoflow-512", True), ("unet3d-256", True),
])
def test_param_count_matches_init_params(arch, smoke):
    cfg = (configs.get_smoke_config(arch) if smoke
           else configs.get_config(arch))
    shapes = jax.eval_shape(lambda k: _init_fn(cfg)(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert cfg.param_count() == n
    if arch == "cosmoflow-128":
        assert n == 9_439_652  # PERF.md §4


# ----------------------------------------------- smoke forward passes ----
@pytest.mark.parametrize("arch", ["cosmoflow-512", "unet3d-256"])
@pytest.mark.parametrize("train", [False, True])
def test_smoke_forward_shape_and_finite(arch, train):
    cfg = configs.get_smoke_config(arch)
    params = _init_fn(cfg)(jax.random.PRNGKey(0), cfg)
    W, n = cfg.input_width, 2
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (n, W, W, W, cfg.in_channels))
    if cfg.arch == "cosmoflow":
        kw = dict(train=True, dropout_rng=jax.random.PRNGKey(3)) \
            if train else {}
        out = jax.jit(lambda p, x: cosmoflow.forward(p, x, cfg, **kw))(
            params, x)
        assert out.shape == (n, cfg.out_dim)
        if train:  # dropout is live: train and eval outputs differ
            eval_out = jax.jit(lambda p, x: cosmoflow.forward(p, x, cfg))(
                params, x)
            assert not np.allclose(np.asarray(out), np.asarray(eval_out))
    else:
        out = jax.jit(lambda p, x: unet3d.forward(p, x, cfg))(params, x)
        assert out.shape == (n, W, W, W, cfg.out_dim)
        if train:
            labels = jnp.zeros((n, W, W, W), jnp.int32)
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p: unet3d.segmentation_loss(p, x, labels, cfg)))(
                    params)
            assert np.isfinite(float(loss))
            for k, g in grads.items():
                assert g.shape == params[k].shape
                assert np.all(np.isfinite(np.asarray(g))), k
    assert np.all(np.isfinite(np.asarray(out)))
