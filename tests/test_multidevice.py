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


def test_cp_attention_matches_reference(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro.core.seq_parallel import cp_attention
from repro.models.layers import chunked_attention

mesh = compat.make_mesh((4,), ('model',))
B, S, H, Hkv, hd = 2, 64, 8, 4, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, hd))
k = jax.random.normal(ks[1], (B, S, Hkv, hd))
v = jax.random.normal(ks[2], (B, S, Hkv, hd))
pos = jnp.arange(S)
ref = chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        kv_chunk=16)
out = jax.jit(lambda q,k,v: cp_attention(q, k, v, mesh, 'model',
                                          causal=True, kv_chunk=16))(q, k, v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
for w in (8, 20, 48):
    outw = jax.jit(lambda q,k,v: cp_attention(q, k, v, mesh, 'model',
        causal=True, window=w, kv_chunk=16))(q, k, v)
    refw = chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                             window=w, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(refw),
                               rtol=2e-5, atol=2e-5)
print("OK")
""")


def test_cp_ssd_and_sharded_decode(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro.core.seq_parallel import (cp_ssd, decode_attention_sharded_kv,
                                     cache_update_sharded)
from repro.models.mamba2 import ssd_chunked
from repro.models.layers import chunked_attention

mesh = compat.make_mesh((4,), ('model',))
B, S, H, P_, N = 2, 64, 4, 8, 16
ks = jax.random.split(jax.random.PRNGKey(1), 5)
x = jax.random.normal(ks[0], (B, S, H, P_))
dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
A = -jnp.exp(jax.random.normal(ks[2], (H,))*0.5)
Bm = jax.random.normal(ks[3], (B, S, N))
Cm = jax.random.normal(ks[4], (B, S, N))
y_ref, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
y_cp = jax.jit(lambda *a: cp_ssd(*a, mesh=mesh, axis='model', chunk=8))(
    x, dt, Bm, Cm) if False else jax.jit(
    lambda x, dt, Bm, Cm: cp_ssd(x, dt, A, Bm, Cm, mesh, 'model', chunk=8))(
    x, dt, Bm, Cm)
np.testing.assert_allclose(np.asarray(y_cp), np.asarray(y_ref),
                           rtol=1e-4, atol=1e-4)

# sharded-KV decode + owner-shard cache update
Hq, hd = 8, 16
k = jax.random.normal(ks[0], (B, S, H, hd))
v = jax.random.normal(ks[1], (B, S, H, hd))
q1 = jax.random.normal(ks[2], (B, 1, Hq, hd))
cur = 37
out = jax.jit(lambda q,k,v: decode_attention_sharded_kv(
    q, k, v, cur, mesh, 'model'))(q1, k, v)
kv_pos = jnp.where(jnp.arange(S) < cur, jnp.arange(S), -1)
ref = chunked_attention(q1, k, v, q_pos=jnp.array([cur-1]), kv_pos=kv_pos,
                        causal=True, kv_chunk=16)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)

new = jax.random.normal(ks[3], (B, 1, H, hd))
upd = jax.jit(lambda c, n: cache_update_sharded(c, n, cur, mesh, 'model'))(
    k, new)
ref_upd = k.at[:, cur:cur+1].set(new)
np.testing.assert_allclose(np.asarray(upd), np.asarray(ref_upd))
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


def test_lm_gspmd_matches_single_device(multidevice):
    """TP-sharded transformer train step == unsharded (GSPMD transparency)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro.configs.base import TransformerConfig
from repro.core.sharding import ShardingPolicy, NO_POLICY
from repro.core.param_specs import infer_param_specs
from repro.models import transformer as T
from repro.optim.adam import Adam, constant

cfg = TransformerConfig(name='t', family='dense', num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=96)
params = T.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 96)
batch = {'tokens': toks, 'labels': toks}
opt = Adam(lr=constant(1e-3))

def step(policy, mesh):
    def fn(p, o, b):
        loss, g = jax.value_and_grad(T.lm_loss)(p, b, cfg, policy, mesh)
        np_, no = opt.update(g, o, p)
        return np_, loss
    return fn

p_ref, l_ref = jax.jit(step(NO_POLICY, None))(params, opt.init(params), batch)

mesh = compat.make_mesh((2, 4), ('data', 'model'))
policy = ShardingPolicy(mesh=mesh, plan='tp')
with compat.set_mesh(mesh):
    p_tp, l_tp = jax.jit(step(policy, mesh))(params, opt.init(params), batch)
assert abs(float(l_ref) - float(l_tp)) < 2e-4
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_tp)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-3, atol=3e-4)

# cp plan too
policy = ShardingPolicy(mesh=mesh, plan='cp')
with compat.set_mesh(mesh):
    p_cp, l_cp = jax.jit(step(policy, mesh))(params, opt.init(params), batch)
assert abs(float(l_ref) - float(l_cp)) < 2e-4
print("OK")
""", devices=8)


def test_ep_moe_and_tp_attention_match_reference(multidevice):
    """§Perf H1/H2 paths: shard_map expert-parallel MoE and head-sharded
    attention are numerically transparent."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import compat
from repro.core.sharding import ShardingPolicy
from repro.core.seq_parallel import tp_attention
from repro.models import moe as moe_lib
from repro.models.layers import chunked_attention

mesh = compat.make_mesh((2, 4), ('data', 'model'))
policy = ShardingPolicy(mesh=mesh, plan='ep')
E, D, F = 4, 32, 64
p = moe_lib.init_moe_params(jax.random.PRNGKey(0), D, F, E)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, D))
with compat.set_mesh(mesh):
    out_ep, aux = jax.jit(lambda p, x: moe_lib.moe_ffn_ep(
        p, x, num_experts=E, top_k=2, mesh=mesh, policy=policy,
        capacity_factor=8.0))(p, x)
out_ref, _ = moe_lib.moe_ffn(p, x, num_experts=E, top_k=2,
                             capacity_factor=8.0)
np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_ref),
                           rtol=2e-4, atol=2e-4)

B, S, H, Hkv, hd = 4, 32, 8, 2, 16
ks = jax.random.split(jax.random.PRNGKey(2), 3)
q = jax.random.normal(ks[0], (B, S, H, hd))
k = jax.random.normal(ks[1], (B, S, Hkv, hd))
v = jax.random.normal(ks[2], (B, S, Hkv, hd))
pos = jnp.arange(S)
ref = chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        kv_chunk=16)
out = jax.jit(lambda q, k, v: tp_attention(
    q, k, v, mesh, 'model', data_axes=('data',), causal=True,
    kv_chunk=16))(q, k, v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("OK")
""")
