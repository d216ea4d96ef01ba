"""Memory subsystem tests (DESIGN.md §9).

Five contracts:

1. Remat equivalence — plan-driven ``jax.checkpoint`` lowering changes
   the jaxpr (remat2 present, only for the marked stages) but not the
   math: loss+grads match the no-remat oracle to <=1e-5, on 1 device and
   under 2-way spatial partitioning, both models. The global
   ``flags.remat`` knob applies exactly when the plan sets no remat.
2. Memory model — ``plan_peak_bytes`` within 15% of the jaxpr-liveness
   measurement across {fp32, bf16} x {remat on/off} x both models; the
   shard_map-aware measurement sees per-device bytes shrink with the
   spatial degree (the paper's aggregate-capacity argument, measured).
3. Precision — bf16/fp16 loss trajectories track the fp32 oracle;
   fp16's dynamic loss scale skips (not corrupts) overflowed steps, at
   the optimizer-wrapper level and end to end.
4. Budgeted planner — a budget below the pure-data-parallel peak forces
   a feasible higher-spatial-degree / remat / lower-precision plan whose
   modeled peak fits (asserted via the model, not a real OOM); an
   impossible budget raises with the closest candidate's breakdown.
5. Satellites — checkpoint manifests record the precision policy and
   canonicalize master weights to fp32; ``opt_state_bytes`` is shared
   between the perf model and the memory model.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import flags, memory, perf_model
from repro.core import plan as plan_lib
from repro.core import precision as precision_lib
from repro.core.perf_model import V100
from repro.models import cosmoflow, unet3d
from repro.optim.adam import Adam, constant


def _smoke_cosmoflow(width=16):
    return dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                               input_width=width)


def _local_plan(cfg):
    """Single-device plan (no mesh axes active)."""
    return plan_lib.uniform_plan(cfg, spatial_axes=(None, None, None))


def _with_remat(plan, flag=True):
    return dataclasses.replace(plan, stages=tuple(
        dataclasses.replace(s, remat=flag) for s in plan.stages))


def _cf_case(cfg, gb=2):
    W = cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W,
                                                  cfg.in_channels))
    y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
    p = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)

    def loss(pl, prec=None):
        return lambda p: cosmoflow.mse_loss(
            p, x, y, cfg, plan=pl, global_batch=gb, train=False,
            precision=prec)

    return p, loss


def _unet_case(gb=2):
    cfg = configs.get_smoke_config("unet3d-256")
    W = cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W,
                                                  cfg.in_channels))
    y = jax.random.randint(jax.random.PRNGKey(1), (gb, W, W, W), 0,
                           cfg.out_dim)
    p = unet3d.init_params(jax.random.PRNGKey(2), cfg)

    def loss(pl, prec=None):
        return lambda p: unet3d.segmentation_loss(
            p, x, y, cfg, plan=pl, global_voxels=gb * W ** 3,
            precision=prec)

    return cfg, p, loss


def _prims(jaxpr):
    return [e.primitive.name for e in jaxpr.eqns]


# ------------------------------------------------------------- contract 1 -
def test_remat_grad_parity_single_device():
    cfg = _smoke_cosmoflow()
    p, loss = _cf_case(cfg)
    base = _local_plan(cfg)
    l0, g0 = jax.value_and_grad(loss(base))(p)
    l1, g1 = jax.value_and_grad(loss(_with_remat(base)))(p)
    assert abs(float(l0) - float(l1)) <= 1e-5
    for k in g0:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   atol=1e-5, rtol=1e-4, err_msg=k)
    ucfg, up, uloss = _unet_case()
    ub = _local_plan(ucfg)
    l0, g0 = jax.value_and_grad(uloss(ub))(up)
    l1, g1 = jax.value_and_grad(uloss(_with_remat(ub)))(up)
    assert abs(float(l0) - float(l1)) <= 1e-5
    for k in g0:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   atol=1e-5, rtol=1e-4, err_msg=k)


def test_remat_jaxpr_structure_and_flag_fallback():
    """remat2 appears exactly for the marked stages; the global flag
    applies only when the plan marks nothing (plan-level remat wins)."""
    cfg = _smoke_cosmoflow()
    p, loss = _cf_case(cfg)
    base = _local_plan(cfg)
    n_blocks = len(cfg.conv_channels)

    def remat_count(pl):
        jx = jax.make_jaxpr(jax.value_and_grad(loss(pl)))(p)
        return sum(1 for n in _prims(jx.jaxpr) if n == "remat2")

    assert remat_count(base) == 0
    assert remat_count(_with_remat(base)) == n_blocks
    # plan remat on the FIRST stage only: only its blocks checkpoint
    one = dataclasses.replace(base, stages=(
        dataclasses.replace(base.stages[0], stop=1, remat=True),
        dataclasses.replace(base.stages[0], start=1)) + base.stages[1:])
    assert one.uses_remat
    assert remat_count(one) == 1
    # no plan-level remat -> the global flag drives every block
    with flags.flags(remat=True):
        assert remat_count(base) == n_blocks
        # ...but a plan that marks stages wins outright over the flag
        assert remat_count(one) == 1


def test_remat_grad_parity_2way_spatial(multidevice):
    """Remat on/off parity for BOTH models under 2-way spatial
    partitioning: the checkpointed bodies re-issue halo/BN collectives in
    backward and still match the no-remat oracle."""
    multidevice("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import compat, plan as plan_lib
from repro import configs
from repro.models import cosmoflow, unet3d

gb = 4
mesh = compat.make_mesh((1, 2), ('data', 'model'))
for arch in ('cosmoflow-512', 'unet3d-256'):
    cfg = configs.get_smoke_config(arch)
    if cfg.arch == 'cosmoflow':
        cfg = dataclasses.replace(cfg, input_width=16)
    W = cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W,
                                                  cfg.in_channels))
    if cfg.arch == 'cosmoflow':
        y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
        params = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)
    else:
        y = jax.random.randint(jax.random.PRNGKey(1), (gb, W, W, W), 0,
                               cfg.out_dim)
        params = unet3d.init_params(jax.random.PRNGKey(2), cfg)
    base = plan_lib.uniform_plan(cfg, spatial_degrees=(2, 1, 1))
    rm = dataclasses.replace(base, stages=tuple(
        dataclasses.replace(s, remat=True) for s in base.stages))
    res = {}
    for name, pl in (('oracle', base), ('remat', rm)):
        def local(p, x, y, _pl=pl):
            def loss_fn(p):
                if cfg.arch == 'cosmoflow':
                    return cosmoflow.mse_loss(
                        p, x, y, cfg, plan=_pl, bn_axes=('data', 'model'),
                        global_batch=gb, train=True,
                        dropout_rng=jax.random.PRNGKey(7),
                        sample_ids=jnp.arange(x.shape[0]))
                return unet3d.segmentation_loss(
                    p, x, y, cfg, plan=_pl, bn_axes=('data', 'model'),
                    global_voxels=gb * W ** 3)
            loss, g = jax.value_and_grad(loss_fn)(p)
            g = jax.tree.map(lambda t: jax.lax.psum(t, ('data', 'model')), g)
            return jax.lax.psum(loss, ('data', 'model')), g
        y_spec = (P('data', 'model') if cfg.arch == 'unet3d'
                  else P('data', None))
        f = jax.jit(compat.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P('data', 'model', None, None, None), y_spec),
            out_specs=(P(), P())))
        res[name] = f(params, x, y)
    (l0, g0), (l1, g1) = res['oracle'], res['remat']
    assert abs(float(l0) - float(l1)) <= 1e-5, arch
    for k in g0:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   atol=1e-5, rtol=1e-4,
                                   err_msg=f"{arch} {k}")
print("OK")
""", devices=4, timeout=560)


# ------------------------------------------------------------- contract 2 -
def test_memory_model_within_15pct_of_measured():
    """The §9 contract: analytic plan walk vs the jaxpr-liveness scan of
    the real forward+backward, across precision x remat, both models."""
    cfg = _smoke_cosmoflow()
    p, loss = _cf_case(cfg)
    base = _local_plan(cfg)
    ucfg, up, uloss = _unet_case()
    ub = _local_plan(ucfg)
    cases = []
    for pl0, params, lf, cname in ((base, p, loss, cfg),
                                   (ub, up, uloss, ucfg)):
        for prec in (None, "bf16"):
            for pl in (pl0, _with_remat(pl0)):
                cases.append((cname, pl, prec, params, lf))
    for ccfg, pl, prec, params, lf in cases:
        measured = memory.trace_peak_bytes(
            jax.value_and_grad(lf(pl, prec)), params)
        modeled = memory.plan_peak_bytes(
            ccfg, pl, global_batch=2, precision=prec,
            include_optimizer=False).total
        ratio = modeled / measured
        assert 0.85 <= ratio <= 1.15, (
            ccfg.name, pl.name, prec,
            f"model {modeled} vs measured {measured} ({ratio:.3f})")


def test_memory_model_structure():
    """Remat and lower precision strictly reduce the modeled peak; the
    spatial degree divides the activation term (aggregate capacity)."""
    cfg = configs.get_config("cosmoflow-256")
    gb = 4
    base = plan_lib.uniform_plan(cfg, spatial_degrees=(1, 1, 1))
    m1 = memory.plan_peak_bytes(cfg, base, global_batch=gb)
    m_rm = memory.plan_peak_bytes(cfg, _with_remat(base), global_batch=gb)
    m_bf = memory.plan_peak_bytes(cfg, base, global_batch=gb,
                                  precision="bf16")
    assert m_rm.total < m1.total
    assert m_bf.total < m1.total
    # float residuals halve; each pool's uint8 window index does not
    index = sum((l.width // l.stride) ** 3 * l.cout * gb / 8
                for l in perf_model.cosmoflow_layers(cfg) if l.pooled)
    assert index > 0
    assert m_bf.activations * 2 - m1.activations == index
    s8 = plan_lib.uniform_plan(cfg, spatial_degrees=(8, 1, 1))
    m8 = memory.plan_peak_bytes(cfg, s8, global_batch=gb)
    # conv residuals divide by the spatial degree; only the (tiny,
    # replicated) FC-head entry does not
    assert m8.activations * 8 == pytest.approx(m1.activations, rel=1e-3)
    # ZeRO-1 shards the optimizer state by the data degree (PR-2)
    dp = plan_lib.uniform_plan(cfg, spatial_degrees=(1, 1, 1),
                               data_degrees=(4,))
    m_rs = memory.plan_peak_bytes(cfg, dp, global_batch=gb,
                                  grad_comm="reduce_scatter")
    m_ov = memory.plan_peak_bytes(cfg, dp, global_batch=gb)
    assert m_rs.opt_state * 4 == m_ov.opt_state


def test_trace_peak_bytes_sees_per_device_shards(multidevice):
    """The liveness scan enters the shard_map body: 2-way spatial local
    peak is measurably below the unpartitioned peak (the capacity
    argument, measured on the traced program)."""
    multidevice("""
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import compat, memory, plan as plan_lib
from repro import configs
from repro.models import cosmoflow

cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          input_width=16)
gb, W = 2, cfg.input_width
x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W, cfg.in_channels))
y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
p = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)

solo = plan_lib.uniform_plan(cfg, spatial_axes=(None, None, None))
peak1 = memory.trace_peak_bytes(
    jax.value_and_grad(lambda p: cosmoflow.mse_loss(
        p, x, y, cfg, plan=solo, global_batch=gb, train=False)), p)

mesh = compat.make_mesh((2,), ('model',))
pl = plan_lib.uniform_plan(cfg, spatial_degrees=(2, 1, 1),
                           data_degrees=(1,))
def local(p, x, y):
    loss = cosmoflow.mse_loss(p, x, y, cfg, plan=pl, bn_axes=('model',),
                              global_batch=gb, train=False)
    return jax.lax.psum(loss, ('model',))
f = compat.shard_map(local, mesh=mesh,
                     in_specs=(P(), P(None, 'model'), P()),
                     out_specs=P())
peak2 = memory.trace_peak_bytes(
    lambda p, x, y: jax.value_and_grad(lambda pp: f(pp, x, y))(p), p, x, y)
assert peak2 < 0.8 * peak1, (peak1, peak2)
print("OK")
""", devices=2, timeout=560)


# ------------------------------------------------------------- contract 3 -
def test_precision_policy_registry():
    assert precision_lib.get(None).name == "fp32"
    assert precision_lib.get("bf16").act_bytes == 2
    assert precision_lib.get(precision_lib.FP16) is precision_lib.FP16
    assert not precision_lib.FP32.uses_scaling
    assert not precision_lib.BF16.needs_wrapper
    assert precision_lib.FP16.uses_scaling
    with pytest.raises(ValueError, match="precision"):
        precision_lib.get("fp8")
    # wrap_optimizer: identity for fp32/bf16, wrapper for fp16, idempotent
    opt = Adam(lr=constant(1e-3))
    assert precision_lib.wrap_optimizer(opt, "bf16") is opt
    w = precision_lib.wrap_optimizer(opt, "fp16")
    assert isinstance(w, precision_lib.MixedPrecision)
    assert precision_lib.wrap_optimizer(w, "fp16") is w


def test_loss_scale_overflow_skip_and_growth():
    policy = dataclasses.replace(precision_lib.FP16, growth_interval=2)
    opt = precision_lib.MixedPrecision(Adam(lr=constant(1e-2),
                                            grad_clip=1.0), policy)
    params = {"w": jnp.ones((4,))}
    st = opt.init(params)
    s0 = float(st.loss_scale)
    # overflow: params AND inner state held, step count frozen, scale /2
    bad = {"w": jnp.full((4,), jnp.inf)}
    p1, st1 = opt.update(bad, st, params)
    assert bool(jnp.all(p1["w"] == params["w"]))
    assert int(st1.inner.step) == 0
    assert float(st1.loss_scale) == s0 / 2
    # finite: step advances, grads unscaled before clipping (a scaled
    # gradient of ||g*scale|| >> clip must produce the same update as
    # the unscaled oracle)
    g = {"w": jnp.full((4,), 3.0)}
    scaled = {"w": g["w"] * st1.loss_scale}
    p2, st2 = opt.update(scaled, st1, params)
    oracle_p, _ = Adam(lr=constant(1e-2), grad_clip=1.0).update(
        g, st1.inner, params)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(oracle_p["w"]),
                               rtol=1e-6)
    assert int(st2.inner.step) == 1
    # growth: after growth_interval consecutive finite steps, scale *2
    p3, st3 = opt.update(scaled, st2, p2)
    assert float(st3.loss_scale) == float(st1.loss_scale) * 2
    assert int(st3.good_steps) == 0


def test_low_precision_loss_tracks_fp32_oracle():
    """bf16/fp16 single-device training trajectories track the fp32
    oracle on the smoke config (bf16's 8-bit mantissa drifts more)."""
    from repro.core import compat
    from repro.train.train_step import (make_convnet_opt_state,
                                        make_convnet_train_step)

    cfg = _smoke_cosmoflow()
    gb, W = 2, cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W,
                                                  cfg.in_channels))
    y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
    p0 = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    traj = {}
    for prec in ("fp32", "bf16", "fp16"):
        opt = Adam(lr=constant(1e-3), grad_clip=1.0)
        step = make_convnet_train_step(cfg, mesh, opt, global_batch=gb,
                                       precision=prec)
        st = make_convnet_opt_state(cfg, opt, p0, mesh=mesh, precision=prec)
        p = jax.tree.map(jnp.copy, p0)
        losses = []
        for s in range(5):
            p, st, loss = step(p, st, x, y, jnp.asarray(s, jnp.int32))
            losses.append(float(loss))
        traj[prec] = losses
        assert all(np.isfinite(l) for l in losses), prec
        # master weights stay fp32 whatever the compute precision
        assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(p))
    for a, b in zip(traj["fp32"], traj["fp16"]):
        assert abs(a - b) <= 0.02 * max(abs(a), 1e-6), (a, b)
    for a, b in zip(traj["fp32"], traj["bf16"]):
        assert abs(a - b) <= 0.20 * max(abs(a), 1e-6), (a, b)


def test_precision_carrying_plan_pairs_step_and_opt_state():
    """A budgeted plan that records its own precision must stay
    self-consistent when BOTH the step and the opt state are built from
    the plan alone (no explicit precision= re-threading)."""
    from repro.core import compat
    from repro.train.train_step import (make_convnet_opt_state,
                                        make_convnet_train_step)

    cfg = _smoke_cosmoflow()
    gb, W = 2, cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W,
                                                  cfg.in_channels))
    y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
    p0 = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    pl = dataclasses.replace(plan_lib.uniform_plan(cfg), precision="fp16")
    opt = Adam(lr=constant(1e-3), grad_clip=1.0)
    step = make_convnet_train_step(cfg, mesh, opt, global_batch=gb, plan=pl)
    st = make_convnet_opt_state(cfg, opt, p0, mesh=mesh, plan=pl)
    assert isinstance(st, precision_lib.MPState)
    p, st, loss = step(jax.tree.map(jnp.copy, p0), st, x, y,
                       jnp.asarray(0, jnp.int32))
    assert np.isfinite(float(loss))
    assert int(st.inner.step) == 1


def test_fp16_overflow_skips_step_e2e():
    """An input engineered to overflow fp16 must leave the params
    untouched and halve the loss scale — not poison the masters."""
    from repro.core import compat
    from repro.train.train_step import (make_convnet_opt_state,
                                        make_convnet_train_step)

    cfg = _smoke_cosmoflow()
    gb, W = 2, cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (gb, W, W, W, cfg.in_channels)) * 1e4
    y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
    p0 = cosmoflow.init_params(jax.random.PRNGKey(2), cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    opt = Adam(lr=constant(1e-3), grad_clip=1.0)
    step = make_convnet_train_step(cfg, mesh, opt, global_batch=gb,
                                   precision="fp16")
    st = make_convnet_opt_state(cfg, opt, p0, mesh=mesh, precision="fp16")
    s0 = float(st.loss_scale)
    p1, st1, _ = step(jax.tree.map(jnp.copy, p0), st, x, y,
                      jnp.asarray(0, jnp.int32))
    for k in p0:
        assert bool(jnp.all(p1[k] == p0[k])), k
    assert int(st1.inner.step) == 0
    assert float(st1.loss_scale) == s0 / 2


# ------------------------------------------------------------- contract 4 -
def test_budgeted_planner_feasible_under_tight_budget():
    """The acceptance scenario: a budget below the pure-data-parallel
    peak for 256^3 CosmoFlow forces a feasible higher-spatial-degree
    and/or remat plan whose modeled peak fits (no real OOM involved)."""
    cfg = configs.get_config("cosmoflow-256")
    gb = 4
    dp = memory.data_parallel_peak_bytes(cfg, global_batch=gb, num_gpus=4)
    budget = 0.5 * dp.total
    assert dp.total > budget  # pure DP would OOM under this budget
    chosen = plan_lib.plan_convnet(
        cfg, V100, spatial_degree=1, data_degree=4, global_batch=gb,
        memory_budget_bytes=budget, spatial_options=(1, 2, 4, 8))
    peak = memory.plan_peak_bytes(cfg, chosen, global_batch=gb)
    assert peak.total <= budget, chosen.name
    ways = 1
    for a in chosen.spatial_axis_names:
        ways *= chosen.degree(a)
    assert ways > 1 or chosen.uses_remat, chosen.name
    # the same search without a budget keeps the pure-DP layout admissible
    free = plan_lib.plan_convnet(cfg, V100, spatial_degree=1,
                                 data_degree=4, global_batch=gb)
    free_peak = memory.plan_peak_bytes(cfg, free, global_batch=gb)
    assert free_peak.total > budget
    # an impossible budget raises with the closest candidate's breakdown
    with pytest.raises(ValueError, match="memory_budget"):
        plan_lib.plan_convnet(
            cfg, V100, spatial_degree=1, data_degree=4, global_batch=gb,
            memory_budget_bytes=1, spatial_options=(1, 2, 4, 8),
            precisions=("fp32", "bf16"))


def test_budgeted_planner_prefers_cheaper_precision_only_when_needed():
    """fp32 stays the choice when it fits; tightening the budget flips
    the SAME search to bf16/remat rather than infeasibility."""
    cfg = configs.get_config("cosmoflow-256")
    gb = 4
    kw = dict(spatial_degree=8, data_degree=1, global_batch=gb,
              precisions=("fp32", "bf16"), remat_options=True)
    roomy = plan_lib.plan_convnet(cfg, V100, memory_budget_bytes=2 ** 34,
                                  **kw)
    assert roomy.precision == "fp32"
    assert not roomy.uses_remat
    m = memory.plan_peak_bytes(cfg, roomy, global_batch=gb)
    tight = plan_lib.plan_convnet(cfg, V100,
                                  memory_budget_bytes=0.6 * m.total, **kw)
    assert tight.precision == "bf16" or tight.uses_remat
    assert memory.plan_peak_bytes(
        cfg, tight, global_batch=gb).total <= 0.6 * m.total


def test_remat_and_precision_pricing():
    """The perf model charges remat's recompute (strictly slower) and
    narrows activation traffic for low precision (never slower)."""
    cfg = configs.get_config("cosmoflow-512")
    base = plan_lib.uniform_plan(cfg, spatial_degrees=(16, 1, 1),
                                 data_degrees=(4,))
    kw = dict(global_batch=64, grad_comm="overlap")
    c0 = plan_lib.price_plan(cfg, V100, base, **kw)
    c_rm = plan_lib.price_plan(cfg, V100, _with_remat(base), **kw)
    assert c_rm > c0
    c_bf = plan_lib.price_plan(
        cfg, V100, dataclasses.replace(base, precision="bf16"), **kw)
    assert c_bf <= c0
    # remat_schedule misuse fails loudly
    with pytest.raises(ValueError, match="remat_schedule"):
        perf_model.iteration_time(cfg, V100, num_gpus=4, ways=2,
                                  global_batch=4,
                                  remat_schedule=[True] * 8)


# ------------------------------------------------------------- contract 5 -
def test_checkpoint_records_precision_and_master_weights(tmp_path):
    from repro.train import checkpoint

    d = str(tmp_path / "ck")
    tree = {"w": jnp.ones((4, 4), jnp.bfloat16),
            "b": jnp.zeros((4,), jnp.float32)}
    checkpoint.save(d, tree, step=7, precision="bf16")
    assert checkpoint.saved_precision(d) == "bf16"
    assert checkpoint.latest_step(d) == 7
    like = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
    restored = checkpoint.restore(d, like)
    # canonical fp32 masters on disk, exactly widened
    assert restored["w"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.ones((4, 4), np.float32))
    # a policy-less save keeps the old manifest shape AND round-trips
    # genuine half-precision leaves exactly (np.save alone would degrade
    # bfloat16 to a raw void dtype)
    d2 = str(tmp_path / "ck2")
    half = {"b": jnp.arange(4, dtype=jnp.bfloat16) / 3,
            "h": jnp.arange(4, dtype=jnp.float16) / 3}
    checkpoint.save(d2, half, step=1)
    assert checkpoint.saved_precision(d2) is None
    back = checkpoint.restore(d2, half)
    assert back["b"].dtype == jnp.bfloat16
    assert back["h"].dtype == jnp.float16
    for k in half:
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(half[k], np.float32))


def test_opt_state_bytes_shared_between_models():
    cfg = configs.get_config("cosmoflow-512")
    n = cfg.param_count()
    r = perf_model.iteration_time(cfg, V100, num_gpus=64, ways=16,
                                  global_batch=64,
                                  grad_comm="reduce_scatter")
    assert r["opt_state_bytes"] == perf_model.opt_state_bytes(
        n, grad_comm="reduce_scatter", data_degree=4)
    pl = plan_lib.uniform_plan(cfg, spatial_degrees=(16, 1, 1),
                               data_degrees=(4,))
    m = memory.plan_peak_bytes(cfg, pl, global_batch=64,
                               grad_comm="reduce_scatter")
    assert m.opt_state == int(perf_model.opt_state_bytes(
        n, grad_comm="reduce_scatter", data_degree=4))
