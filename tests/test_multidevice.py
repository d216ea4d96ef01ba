"""Distributed-semantics tests, each in a subprocess with 8 forced host
devices (the main pytest process keeps the real 1-device CPU, per the
assignment). These are the system's core invariants: sharded == unsharded.
"""
import pytest


def test_spatial_conv_bn_pool_matches_unsharded(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from jax.sharding import PartitionSpec as P
from repro.core.spatial_conv import SpatialPartitioning, conv3d, maxpool3d
from repro.core import dist_norm
import jax.lax as lax

mesh = compat.make_mesh((2, 4), ('data', 'model'))
part = SpatialPartitioning(('model', None, None))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 8, 8, 3))
w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 3, 8)) * 0.1
scale, bias = jnp.ones(8), jnp.zeros(8)

def local_fn(x, w, scale, bias):
    h = conv3d(x, w, part, stride=1)
    h = dist_norm.distributed_batchnorm(h, scale, bias, ('data', 'model'))
    return maxpool3d(h, part)

f = jax.jit(compat.shard_map(local_fn, mesh=mesh,
    in_specs=(P('data', 'model'), P(), P(), P()),
    out_specs=P('data', 'model')))
out = f(x, w, scale, bias)

ref = lax.conv_general_dilated(x, w, (1,1,1), 'SAME',
    dimension_numbers=("NDHWC","DHWIO","NDHWC"))
m = ref.mean(axis=(0,1,2,3)); v = ref.var(axis=(0,1,2,3))
ref = (ref - m) * jax.lax.rsqrt(v + 1e-5) * scale + bias
ref = lax.reduce_window(ref, -jnp.inf, lax.max, (1,2,2,2,1), (1,2,2,2,1),
                        'VALID')
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
# gradient flows correctly through the halo exchange
def lfull(w):
    h = compat.shard_map(lambda x, w: conv3d(x, w, part), mesh=mesh,
        in_specs=(P('data','model'), P()), out_specs=P('data','model'))(x, w)
    return jnp.mean(h**2)
gw = jax.jit(jax.grad(lfull))(w)
def lref(w):
    h = lax.conv_general_dilated(x, w, (1,1,1), 'SAME',
        dimension_numbers=("NDHWC","DHWIO","NDHWC"))
    return jnp.mean(h**2)
np.testing.assert_allclose(np.asarray(gw), np.asarray(jax.grad(lref)(w)),
                           rtol=2e-4, atol=2e-5)
print("OK")
""")


def test_convnet_train_step_matches_single_device(multidevice):
    """The paper's hybrid-parallel train step produces the same params as a
    1x1-mesh run (spatial+data partitioning is semantically transparent)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro import configs
from repro.models import cosmoflow
from repro.optim.adam import Adam, constant
from repro.train.train_step import make_convnet_train_step

cfg = configs.get_smoke_config('cosmoflow-512')
gb = 4
key = jax.random.PRNGKey(0)
W = cfg.input_width
x = jax.random.normal(key, (gb, W, W, W, cfg.in_channels))
y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
params0 = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)

results = []
for shape in ((1, 1), (2, 4)):
    mesh = compat.make_mesh(shape, ('data', 'model'))
    opt = Adam(lr=constant(1e-3))
    step = make_convnet_train_step(cfg, mesh, opt,
        spatial_axes=('model', None, None), data_axes=('data',),
        global_batch=gb)
    p, o, loss = step(jax.tree.map(jnp.copy, params0),
                      opt.init(params0), x, y, jnp.asarray(7, jnp.int32))
    results.append((jax.device_get(p), float(loss)))

(p1, l1), (p8, l8) = results
assert abs(l1 - l8) < 2e-5, (l1, l8)
# Adam's rsqrt(v) amplifies fp32 reduction-order noise on first steps;
# losses match tightly; params see fp32 reduction-order noise (psum over 8
# ranks + the shard-local conv decomposition) amplified through rsqrt(v) on
# the first step — a handful of elements land near 2e-3.
for k in p1:
    np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p8[k]),
                               rtol=3e-3, atol=2e-3)
print("OK")
""", devices=8)


def test_cosmoflow_gradient_spatial4_matches_single_device(multidevice):
    """At spatial=4 every local depth the pools see is even, so they take
    the saved-window-index path on each shard (no select-and-scatter in
    the step); one SGD step at lr 1 with no momentum reads the gradient
    back (p0 - p1), which must match the one-device gradient."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro import configs
from repro.models import cosmoflow
from repro.optim.adam import SGD, constant
from repro.train.train_step import make_convnet_train_step

cfg = configs.get_smoke_config('cosmoflow-512')
gb = 2
W = cfg.input_width
x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W, cfg.in_channels))
y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
params0 = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)

grads, losses = [], []
for shape in ((1, 1), (1, 4)):
    mesh = compat.make_mesh(shape, ('data', 'model'))
    opt = SGD(lr=constant(1.0), momentum=0.0)
    step = make_convnet_train_step(cfg, mesh, opt,
        spatial_axes=('model', None, None), data_axes=('data',),
        global_batch=gb)
    args = (jax.tree.map(jnp.copy, params0), opt.init(params0), x, y,
            jnp.asarray(3, jnp.int32))
    assert 'select_and_scatter' not in step.lower(*args).as_text()
    p, _, loss = step(*args)
    grads.append({k: np.asarray(params0[k]) - np.asarray(p[k]) for k in p})
    losses.append(float(loss))

assert abs(losses[0] - losses[1]) < 2e-5, losses
for k in grads[0]:
    g1, g4 = grads[0][k], grads[1][k]
    scale = max(np.abs(g1).max(), 1e-6)
    np.testing.assert_allclose(g4 / scale, g1 / scale, rtol=0, atol=1e-4,
                               err_msg=k)
print("OK")
""", devices=4)
