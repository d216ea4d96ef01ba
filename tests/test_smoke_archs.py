"""Per-architecture SMOKE tests.

Each paper model's REDUCED variant (same family, input width <= 32) runs
one train step on a trivial 1x1 mesh on the 1-device CPU, asserting a
finite loss and finite updated params.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import compat
from repro.optim.adam import Adam, constant


@pytest.mark.parametrize("arch", ["cosmoflow-512", "unet3d-256"])
def test_smoke_convnet_train_step(arch):
    """Reduced conv-net variants on a trivial 1x1 mesh (1 CPU device)."""
    from repro.models import cosmoflow, unet3d
    from repro.train.train_step import make_convnet_train_step
    cfg = configs.get_smoke_config(arch)
    assert cfg.input_width <= 32
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    opt = Adam(lr=constant(1e-3))
    gb = 2
    step = make_convnet_train_step(
        cfg, mesh, opt, spatial_axes=("model", None, None),
        data_axes=("data",), global_batch=gb)
    key = jax.random.PRNGKey(0)
    W = cfg.input_width
    x = jax.random.normal(key, (gb, W, W, W, cfg.in_channels))
    if cfg.arch == "unet3d":
        y = jax.random.randint(jax.random.PRNGKey(1), (gb, W, W, W), 0,
                               cfg.out_dim)
        params = unet3d.init_params(jax.random.PRNGKey(2), cfg)
    else:
        y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
        params = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)
    opt_state = opt.init(params)
    params, opt_state, loss = step(params, opt_state, x, y,
                                   jnp.asarray(0, jnp.int32))
    assert np.isfinite(float(loss)), arch
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf))), arch
