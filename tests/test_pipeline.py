"""Pipeline parallelism as a third plan axis (DESIGN.md §13).

Four contracts:

* **Schedule** — ``_schedule_order`` emits a topologically valid order;
  1F1B keeps the canonical forward-before-backward steady-state pairs
  (the in-flight window that makes the schedule overlap at all) and the
  sequential oracle drains every micro-batch behind a SYNC.
* **Equivalence** — 1F1B == sequential bitwise at any micro-batch count
  (same jits, same accumulation order); == no-pipeline to fp tolerance
  (sum of per-micro losses/grads is the full-batch value; per-micro BN
  statistics are the one excluded term, so multi-micro parity runs with
  batchnorm off). Micro-batch backward still fires §4's bucketed
  gradient reductions (jaxpr), and a pipelined Session checkpoint
  round-trips bitwise with the group mapping serialized.
* **Planner** — the joint (data x spatial x pipeline) argmin never
  picks a pipelined plan priced above the best non-pipelined candidate,
  and a memory budget only the pipelined split fits forces the choice
  (micro-batching shrinks per-device activations — the capacity lever).
* **Config** — RunConfig names the offending field and a concrete fix.
"""
import dataclasses

import pytest

from repro import configs
from repro.api import RunConfig
from repro.api.config import RunConfigError
from repro.core import memory as memory_lib
from repro.core import perf_model
from repro.core import plan as plan_lib
from repro.core.perf_model import V100
from repro.train.train_step import _schedule_order


# ---------------------------------------------------------------- schedule

def _check_valid(order, K, M):
    """Every op exactly once, every data dependency before its consumer."""
    done = set()
    for op, k, m in order:
        if op == "SYNC":
            continue
        assert (op, k, m) not in done
        if op == "F" and k > 0:
            assert ("F", k - 1, m) in done, (op, k, m)
        if op == "FB":
            assert K == 1 or ("F", k - 1, m) in done, (op, k, m)
        if op == "B":
            up = ("FB", K - 1, m) if k == K - 2 else ("B", k + 1, m)
            assert up in done, (op, k, m)
        done.add((op, k, m))
    want = {("F", k, m) for k in range(K - 1) for m in range(M)}
    want |= {("FB", K - 1, m) for m in range(M)}
    want |= {("B", k, m) for k in range(K - 2, -1, -1) for m in range(M)}
    assert done == want


@pytest.mark.parametrize("K,M", [(2, 1), (2, 8), (3, 4), (4, 6)])
def test_schedule_order_valid(K, M):
    _check_valid(_schedule_order(K, M, "1f1b"), K, M)
    seq = _schedule_order(K, M, "sequential")
    _check_valid(seq, K, M)
    # the oracle drains: one SYNC per micro-batch, after its backward
    syncs = [m for op, _, m in seq if op == "SYNC"]
    assert syncs == list(range(M))


@pytest.mark.parametrize("K,M", [(2, 8), (3, 8), (4, 8)])
def test_1f1b_keeps_forward_window_open(K, M):
    """The canonical 1F1B order: after node k's min(K-1-k, M) warmup
    forwards, each steady-state pair enqueues the NEXT forward before
    the backward — backward-first would collapse the in-flight window
    to one micro-batch and serialize the schedule through every stage
    boundary (the window is what the link-latency bench measures)."""
    order = _schedule_order(K, M, "1f1b")
    for k in range(K - 1):
        sub = [(op, m) for op, k_, m in order if k_ == k]
        warm = min(K - 1 - k, M)
        first_b = sub.index(("B", 0))
        fwds_before = [m for op, m in sub[:first_b] if op == "F"]
        assert fwds_before == list(range(min(warm + 1, M))), (k, sub[:6])


# ------------------------------------------------------------- perf model

def test_model_prices_bubble_vs_drain():
    cfg = configs.get_config("cosmoflow-512")
    n = plan_lib.cosmoflow_n_layers(cfg)
    kw = dict(group_ranges=((0, 4), (4, n)), data_degree=4,
              micro_batches=8, global_batch=32)
    r1 = perf_model.pipeline_iteration_time(cfg, V100, schedule="1f1b", **kw)
    rs = perf_model.pipeline_iteration_time(cfg, V100,
                                            schedule="sequential", **kw)
    # 1f1b pays the (P-1)/(M+P-1) bubble; sequential pays the full
    # M * sum(stages) drain — strictly worse for M > 1
    assert r1["bubble_fraction"] == pytest.approx(1 / 9)
    assert rs["total"] > r1["total"] * 1.4, (rs["total"], r1["total"])


def test_group_param_counts_partition_total():
    cfg = configs.get_config("cosmoflow-512")
    n = plan_lib.cosmoflow_n_layers(cfg)
    gp = perf_model.group_param_counts(cfg, ((0, 3), (3, n)))
    assert sum(gp) == pytest.approx(cfg.param_count())
    assert all(g > 0 for g in gp)


def test_pipeline_peak_shrinks_with_micro_batches():
    """The capacity lever: the recompute contract stores only boundary
    activations per in-flight micro, so peak bytes FALL as the
    micro-batch count rises; the drained sequential oracle holds a
    strictly smaller window than 1F1B."""
    cfg = configs.get_config("cosmoflow-512")
    gb = 32

    def peak(m, sched="1f1b"):
        plan = plan_lib.pipelined_convnet_plan(
            cfg, boundaries=(4,), micro_batches=m, schedule=sched,
            data_degrees=(4,))
        return memory_lib.plan_peak_bytes(cfg, plan, global_batch=gb).total

    assert peak(8) < peak(4) < peak(2)
    assert peak(8, "sequential") <= peak(8)
    # and the split is charged per GROUP, not whole-network: the
    # pipelined peak at m=8 undercuts pure data parallelism
    base = plan_lib.plan_convnet(cfg, V100, spatial_degree=1,
                                 data_degree=8, global_batch=gb)
    base_peak = memory_lib.plan_peak_bytes(cfg, base, global_batch=gb)
    assert peak(8) < base_peak.total / 2


# ---------------------------------------------------------------- planner

def test_planner_never_picks_overpriced_pipeline():
    cfg = configs.get_config("cosmoflow-512")
    kw = dict(spatial_degree=1, data_degree=8, global_batch=32,
              grad_comm="overlap")
    base = plan_lib.plan_convnet(cfg, V100, **kw)
    joint = plan_lib.plan_convnet(cfg, V100, pipeline_options=(2,),
                                  micro_batch_options=(8,), **kw)
    # every pipelined candidate is priced above the data-parallel plan
    # here, so the joint argmin must return the same non-pipelined plan
    cands = plan_lib.candidate_pipeline_plans(
        cfg, V100, pipeline_degrees=(2,), micro_batch_options=(8,),
        num_devices=8, global_batch=32)
    assert min(c.cost for c in cands) > base.cost
    assert joint.n_groups == 1 and joint.cost == base.cost


def test_planner_budget_forces_pipeline():
    cfg = configs.get_config("cosmoflow-512")
    gb = 32
    kw = dict(spatial_degree=1, data_degree=8, global_batch=gb,
              grad_comm="overlap")
    chosen = plan_lib.plan_convnet(
        cfg, V100, memory_budget_bytes=100 * 2 ** 30,
        pipeline_options=(2,), micro_batch_options=(8,), **kw)
    assert chosen.n_groups == 2
    assert chosen.pipeline.micro_batches == 8
    peak = memory_lib.plan_peak_bytes(cfg, chosen, global_batch=gb)
    assert peak.total <= 100 * 2 ** 30


def test_pipelined_plan_validates_boundaries():
    cfg = configs.get_smoke_config("cosmoflow-512")
    with pytest.raises(ValueError, match="boundaries"):
        plan_lib.pipelined_convnet_plan(cfg, boundaries=(0,))
    with pytest.raises(ValueError, match="boundaries"):
        plan_lib.pipelined_convnet_plan(cfg, boundaries=(2, 2))


# ----------------------------------------------------------------- config

def test_runconfig_pipeline_field_errors():
    cfg = configs.get_smoke_config("cosmoflow-512")

    def err(**kw):
        with pytest.raises(RunConfigError) as e:
            RunConfig(model=cfg, global_batch=8, **kw).validate(
                device_count=8)
        return str(e.value)

    msg = err(data=4, pipeline=3)
    assert "pipeline" in msg and "multiple" in msg
    msg = err(data=4, pipeline=2, spatial=2)
    assert "spatial" in msg
    msg = err(data=4, pipeline=0)
    assert "pipeline" in msg
    msg = err(data=4, pipeline=2, grad_comm="reduce_scatter")
    assert "reduce_scatter" in msg or "grad_comm" in msg


# ----------------------------------------------- runtime (multi-device)

def test_pipeline_parity_cosmoflow(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.core import plan as plan_lib
from repro.launch import mesh as mesh_lib
from repro.train import train_step as ts
from repro.optim.adam import Adam
from repro.models import cosmoflow as cf

cfg = configs.get_smoke_config('cosmoflow-512')
gb = 8
params = cf.init_params(jax.random.PRNGKey(0), cfg)
kx, ky = jax.random.split(jax.random.PRNGKey(1))
x = np.asarray(jax.random.normal(
    kx, (gb,) + (cfg.input_width,) * 3 + (cfg.in_channels,)), np.float32)
y = np.asarray(jax.random.normal(ky, (gb, cfg.out_dim)), np.float32)
opt = Adam(lambda s: 1e-3)

mesh = mesh_lib.make_local_mesh(model=1, data=4)
step_ref = ts.make_convnet_train_step(
    cfg, mesh, opt, spatial_axes=(None, None, None), data_axes=('data',),
    global_batch=gb, grad_comm='overlap')
o_ref = ts.make_convnet_opt_state(cfg, opt, params, grad_comm='overlap')
p_ref = jax.tree.map(jnp.copy, params)
for s in range(3):
    p_ref, o_ref, l_ref = step_ref(p_ref, o_ref, x, y, s)

def run_pipe(M, schedule, mode='overlap', guard=False):
    plan = plan_lib.pipelined_convnet_plan(
        cfg, boundaries=(2,), micro_batches=M, schedule=schedule,
        data_degrees=(2,))
    meshes = mesh_lib.make_pipeline_meshes(plan)
    step = ts.make_pipeline_train_step(
        cfg, meshes, opt, plan=plan, global_batch=gb, grad_comm=mode,
        guard=guard)
    p = jax.tree.map(jnp.copy, params)
    o = ts.make_pipeline_opt_state(cfg, opt, p, plan=plan, meshes=meshes)
    for s in range(3):
        out = step(p, o, x, y, s)
        p, o, l = out[:3]
    if guard:
        assert float(out[3]) == 1.0, 'guard skipped a clean step'
    return p, float(l)

def maxdiff(a, b):
    return max(float(np.max(np.abs(np.asarray(a[k], np.float32) -
                                   np.asarray(b[k], np.float32))))
               for k in a)

# M=1: one micro-batch IS the batch (BN included) -> fp-tolerance parity.
# The BN statistics are psum'd in another grouping, and Adam's first
# steps move each param by about +-lr whatever its gradient's size, so a
# near-zero gradient element whose fp32 rounding flips its sign drifts
# apart by up to 2*lr per step: 6e-3 over the 3 steps at lr 1e-3.
p1, l1 = run_pipe(1, '1f1b')
assert abs(l1 - float(l_ref)) <= 1e-4, (l1, float(l_ref))
assert maxdiff(p1, p_ref) <= 2 * 1e-3 * 3, maxdiff(p1, p_ref)

# M=4: 1f1b vs the sequential oracle is BITWISE (same jits, same order)
p2, l2 = run_pipe(4, '1f1b')
p3, l3 = run_pipe(4, 'sequential')
assert l2 == l3 and maxdiff(p2, p3) == 0.0, (l2, l3)

# grad-comm lowerings agree under micro-batching; guard composes
p4, l4 = run_pipe(4, '1f1b', mode='monolithic')
assert l4 == l2 and maxdiff(p4, p2) == 0.0
run_pipe(2, '1f1b', guard=True)
print('OK')
""", devices=4)


def test_pipeline_bitwise_unet(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.core import plan as plan_lib
from repro.launch import mesh as mesh_lib
from repro.train import train_step as ts
from repro.optim.adam import Adam
from repro.models import unet3d as un

cfg = configs.get_smoke_config('unet3d-256')
gb = 8
params = un.init_params(jax.random.PRNGKey(0), cfg)
kx, ky = jax.random.split(jax.random.PRNGKey(1))
x = np.asarray(jax.random.normal(
    kx, (gb,) + (cfg.input_width,) * 3 + (cfg.in_channels,)), np.float32)
y = np.asarray(jax.random.randint(
    ky, (gb,) + (cfg.input_width,) * 3, 0, cfg.out_dim), np.int32)
opt = Adam(lambda s: 1e-3)

def run_pipe(M, schedule):
    plan = plan_lib.pipelined_convnet_plan(
        cfg, boundaries=(1,), micro_batches=M, schedule=schedule,
        data_degrees=(2,))
    meshes = mesh_lib.make_pipeline_meshes(plan)
    step = ts.make_pipeline_train_step(
        cfg, meshes, opt, plan=plan, global_batch=gb, grad_comm='overlap')
    p = jax.tree.map(jnp.copy, params)
    o = ts.make_pipeline_opt_state(cfg, opt, p, plan=plan, meshes=meshes)
    for s in range(2):
        p, o, l = step(p, o, x, y, s)
    return p, float(l)

def maxdiff(a, b):
    return max(float(np.max(np.abs(np.asarray(a[k], np.float32) -
                                   np.asarray(b[k], np.float32))))
               for k in a)

# the V-cycle chain (down/core/up + cross-group skip cotangents) is
# bitwise-deterministic across schedules too
p2, l2 = run_pipe(2, '1f1b')
p3, l3 = run_pipe(2, 'sequential')
assert l2 == l3 and maxdiff(p2, p3) == 0.0, (l2, l3)
print('OK')
""", devices=4)


def test_micro_backward_fires_bucketed_reductions(multidevice):
    multidevice("""
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.core import compat, grad_comm
from repro.core import plan as plan_lib
from repro.models import cosmoflow
from repro.train.train_step import pipeline_group_params

# no BN: every psum in the program is a gradient reduction
cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          batchnorm=False)
W = cfg.input_width
plan = plan_lib.pipelined_convnet_plan(cfg, boundaries=(2,),
                                       micro_batches=4, data_degrees=(2,))
a, b = plan.group_layer_ranges()[0]
params = jax.tree.map(
    lambda s: jnp.zeros(s.shape, s.dtype),
    jax.eval_shape(lambda k: cosmoflow.init_params(k, cfg),
                   jax.random.PRNGKey(0)))
gparams = pipeline_group_params(cfg, plan, params)[0]

mesh = compat.make_mesh((2,), ('data',))
h = jnp.zeros((2, W, W, W, cfg.in_channels))

def bwd(p, h):  # the runtime's non-last backward node, verbatim shape
    def f(p_, h_):
        return cosmoflow.forward_range(p_, h_, cfg, a, b,
                                       bn_axes=('data',), train=True,
                                       grad_axes=('data',))
    out, vjp = jax.vjp(f, p, h)
    return vjp(jnp.ones_like(out))

f = compat.shard_map(bwd, mesh=mesh, in_specs=(P(), P('data')),
                     out_specs=(P(), P('data')))

def find_jaxpr_with(jaxpr, prim):
    if any(e.primitive.name == prim for e in jaxpr.eqns):
        return jaxpr
    for e in jaxpr.eqns:
        for v in e.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                if hasattr(item, 'jaxpr'):
                    item = item.jaxpr
                if hasattr(item, 'eqns'):
                    r = find_jaxpr_with(item, prim)
                    if r is not None:
                        return r
    return None

# one hook per leaf: under the default policy both smoke conv kernels
# coalesce into one flat bucket, whose single psum can only follow the
# group's last weight cotangent, so no reduction could precede compute
with grad_comm.bucket_policy(small_thresh_elems=1):
    bplan = grad_comm.make_plan(gparams)
    body = find_jaxpr_with(jax.make_jaxpr(f)(gparams, h).jaxpr, 'psum')
assert bplan.num_buckets == 2, bplan.num_buckets
names = [e.primitive.name for e in body.eqns]
n_psum = names.count('psum')
# per-micro backward reduces through the SAME bucket hooks as the
# non-pipelined step: one psum per bucket of the group's params
assert n_psum == bplan.num_buckets, (n_psum, bplan.num_buckets)
compute = [i for i, n in enumerate(names)
           if n in ('conv_general_dilated', 'dot_general')]
psums = [i for i, n in enumerate(names) if n == 'psum']
assert sum(1 for p in psums if any(c > p for c in compute)) >= 1
print('OK')
""", devices=4)


def test_pipeline_checkpoint_roundtrip(multidevice):
    multidevice("""
import glob, tempfile
import jax, numpy as np
from repro import configs
from repro.api import RunConfig, compile as api_compile
from repro.api.session import Session

cfg = configs.get_smoke_config('cosmoflow-512')
gb = 8
sess = api_compile(RunConfig(model=cfg, global_batch=gb, plan='fixed',
                             data=4, pipeline=2, micro_batches=4,
                             lr=1e-3, grad_clip=0.0))
rep = sess.describe()
assert rep.stage_groups is not None and rep.micro_batches == 4
assert rep.bubble_fraction is not None

kx, ky = jax.random.split(jax.random.PRNGKey(1))
x = np.asarray(jax.random.normal(
    kx, (gb,) + (cfg.input_width,) * 3 + (cfg.in_channels,)), np.float32)
y = np.asarray(jax.random.normal(ky, (gb, cfg.out_dim)), np.float32)
sess.step(x, y)
ckpt = tempfile.mkdtemp()
sess.save(ckpt)
l_next = float(sess.step(x, y))

sess2 = Session.restore(ckpt)
assert sess2.plan.n_groups == 2
assert sess2.plan.pipeline.micro_batches == 4
# bitwise: the restored pipelined session replays the same step
assert float(sess2.step(x, y)) == l_next

# the serialized run records the pipeline axis (group mapping restores)
blob = ''.join(open(f).read() for f in glob.glob(ckpt + '/**/*.json',
                                                 recursive=True))
assert 'stage_groups' in blob and 'micro_batches' in blob
print('OK')
""", devices=4)
