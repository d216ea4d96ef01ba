"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what interpret mode never sees: kernels
whose blocks do not fit VMEM or break the tiling, and steps that do not
fit HBM. These tests compile, at CosmoFlow-128's real shapes (global
batch 4, fp32), the three ``use_pallas`` kernels for every layer the
step hands them, the one-chip train step, and the spatial=4 step on a
2x2 slice. Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.api.config import RunConfig
from repro.core import flags
from repro.models import cosmoflow

HBM_BYTES = 16 * 2 ** 30
GLOBAL_BATCH = 4
MODEL = "cosmoflow-128"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _layers(cfg):
    """(block, input width, cin, cout, stride, conv output width) of each
    CosmoFlow conv block at spatial=1."""
    w, cin = cfg.input_width, cfg.in_channels
    for i, cout in enumerate(cfg.conv_channels[:cosmoflow.num_blocks(cfg)]):
        stride = 2 if i == 3 else 1
        wo = w // stride
        yield i, w, cin, cout, stride, wo
        w = wo // 2 if i < cosmoflow.num_pools(cfg) else wo
        cin = cout


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    cfg = configs.get_config(MODEL)
    n = GLOBAL_BATCH
    cases = []
    for i, _, cin, cout, _, wo in _layers(cfg):
        cases.append(("conv3d", f"block{i}", (cout, 27 * cin),
                      (27 * cin, n * wo ** 3)))
        cases.append(("bn_act", f"block{i}", (n, wo, wo, wo, cout), None))
    # the first partitioned layer at spatial=4: the overlapped conv's
    # interior piece, its normalize pass, and the depth-halo kernels
    w, cin, cout = cfg.input_width, cfg.in_channels, cfg.conv_channels[0]
    d = w // 4
    cases.append(("conv3d", "block0-spatial4-interior", (cout, 27 * cin),
                  (27 * cin, n * (d - 2) * w * w)))
    cases.append(("bn_act", "block0-spatial4", (n, d, w, w, cout), None))
    cases.append(("halo_pack", "block0-spatial4", (n, d, w, w, cin), None))
    cases.append(("halo_unpack", "block0-spatial4", (n, d, w, w, cin),
                  None))
    return [pytest.param(*c, id=f"{c[0]}-{c[1]}") for c in cases]


@pytest.mark.parametrize("kernel,layer,a,b", _kernel_cases())
def test_kernel_compiles_at_cosmoflow128_shapes(topo, kernel, layer, a, b):
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.bn_act.kernel import bn_leaky_relu
    from repro.kernels.conv3d.kernel import conv3d_gemm
    from repro.kernels.halo_pack.kernel import pack_depth, unpack_depth

    one = SingleDeviceSharding(topo.devices[0])
    if kernel == "conv3d":
        fn, args = conv3d_gemm, (_sds(a, one), _sds(b, one))
    elif kernel == "bn_act":
        c = a[-1]
        fn = bn_leaky_relu
        args = (_sds(a, one),) + tuple(_sds((c,), one) for _ in range(4))
    elif kernel == "halo_pack":
        fn, args = (lambda x: pack_depth(x, 1, 1)), (_sds(a, one),)
    else:
        face = a[:1] + (1,) + a[2:]
        fn = unpack_depth
        args = (_sds(a, one), _sds(face, one), _sds(face, one))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (kernel, layer)


def _abstract_step(topo, spatial, use_pallas=False):
    """The train step ``compile(RunConfig(model, global_batch=4,
    spatial=..., use_pallas=...))`` builds, lowered from shapes on
    described chips."""
    from repro.api import session as session_lib
    from repro.train import train_step as ts

    config = RunConfig(model=MODEL, global_batch=GLOBAL_BATCH,
                       spatial=spatial, use_pallas=use_pallas)
    cfg = config.resolve_model()
    grad_comm = flags.get("grad_comm")
    plan, precision = session_lib._resolve_plan(config, cfg, grad_comm)
    shape = tuple(n for _, n in plan.mesh_axes)
    mesh = Mesh(np.asarray(topo.devices[:spatial]).reshape(shape),
                tuple(a for a, _ in plan.mesh_axes))
    optimizer = session_lib._build_optimizer(config)
    step = ts.make_convnet_train_step(
        cfg, mesh, optimizer, global_batch=GLOBAL_BATCH, plan=plan,
        precision=precision, grad_comm=grad_comm, guard=True,
        use_pallas=config.use_pallas)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(
        lambda: cosmoflow.init_params(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(
        lambda p: ts.make_convnet_opt_state(
            cfg, optimizer, p, grad_comm=grad_comm, plan=plan,
            precision=precision), params)
    w = cfg.input_width
    args = (
        jax.tree.map(lambda s: _sds(s.shape, rep, s.dtype), params),
        jax.tree.map(lambda s: _sds(s.shape, rep, s.dtype), opt_state),
        _sds((GLOBAL_BATCH, w, w, w, cfg.in_channels),
             NamedSharding(mesh, P("data", "model"))),
        _sds((GLOBAL_BATCH, cfg.out_dim), NamedSharding(mesh, P("data"))),
        _sds((), rep, jnp.int32))
    return step.lower(*args).compile()


def _step_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_train_step_fits_one_chip(topo):
    total = _step_bytes(_abstract_step(topo, 1))
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


def test_pallas_train_step_fits_one_chip(topo):
    """The conv3d kernel's patch matrix, 27x its input, is built a
    bounded chunk at a time: whole, it took the step to 33.85 GiB."""
    total = _step_bytes(_abstract_step(topo, 1, use_pallas=True))
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


def test_spatial4_step_exchanges_halos(topo):
    compiled = _abstract_step(topo, 4)
    assert "collective-permute" in compiled.as_text()
