"""Public API (DESIGN.md §10): RunConfig validation, compile/Session
lifecycle, config round-trip, loader specs, driver hygiene, and the
Session-vs-raw-path parity + restore contracts on a hybrid mesh."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro import configs
from repro.api import RunConfig, RunConfigError, Session
from repro.api import compile as api_compile
from repro.api.config import (conv_config_from_json, plan_from_json,
                              plan_to_json)
from repro.core import plan as plan_lib


def _smoke(width=16):
    return dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                               input_width=width)


# ------------------------------------------------- RunConfig validation ----
@pytest.mark.parametrize("field,kw,fix_hint", [
    ("model", dict(model="cosmoflw-512"), "cosmoflow-512"),
    ("model", dict(model="gemma2-2b"), "unknown model"),
    ("model", dict(model="cosmoflow-12"),
     "did you mean cosmoflow-512, cosmoflow-128"),
    ("precision", dict(model="unet3d-256", smoke=True, precision="f32"),
     "fp32"),
    ("grad_comm", dict(model="unet3d-256", smoke=True, grad_comm="zero"),
     "reduce_scatter"),
    ("global_batch", dict(model="unet3d-256", smoke=True, global_batch=3,
                          data=2), "multiple of 2"),
    ("spatial", dict(model="unet3d-256", smoke=True, spatial=8,
                     data=1), "<= 4"),
    ("plan", dict(model="unet3d-256", smoke=True, plan="greedy"), "fixed"),
    ("lr_schedule", dict(model="unet3d-256", smoke=True,
                         lr_schedule="cosine"), "linear_decay"),
    ("save_every", dict(model="unet3d-256", smoke=True, save_every=10),
     "checkpoint_dir"),
    ("data", dict(model="unet3d-256", smoke=True, data=64,
                  global_batch=64),
     "xla_force_host_platform_device_count"),
])
def test_validation_names_field_and_fix(field, kw, fix_hint):
    """Misconfigurations raise RunConfigError naming the offending field
    and a concrete fix (the ISSUE's >=5 cases and then some)."""
    with pytest.raises(RunConfigError) as ei:
        RunConfig(**kw).validate(device_count=8)
    assert ei.value.field == field
    assert f"RunConfig.{field}" in str(ei.value)
    assert fix_hint in str(ei.value)


def test_validation_plan_degree_mismatch():
    cfg = _smoke()
    pl = plan_lib.uniform_plan(cfg, spatial_degrees=(2, 1, 1),
                               data_degrees=(2,))
    with pytest.raises(RunConfigError, match="data=2, spatial=2"):
        RunConfig(model=cfg, plan=pl, data=1, spatial=1).validate(
            device_count=8)
    # and the matching degrees pass
    RunConfig(model=cfg, plan=pl, data=2, spatial=2,
              global_batch=4).validate(device_count=8)


def test_budget_below_feasible_reports_floor():
    """An impossible budget errors with the min feasible budget from the
    memory model (not a bare 'no plan fits')."""
    with pytest.raises(RunConfigError, match="raise to at least") as ei:
        api_compile(RunConfig(model=_smoke(), global_batch=2,
                              memory_budget_gib=1e-6))
    assert ei.value.field == "memory_budget_gib"
    assert "GiB" in ei.value.fix


# ------------------------------------------------------ serialization ----
def test_config_json_roundtrip_with_inline_model_and_plan():
    cfg = _smoke()
    pl = plan_lib.convnet_plan(cfg, boundary=1, kind="batch",
                               spatial_degrees=(1, 1, 1))
    config = RunConfig(model=cfg, plan=pl, global_batch=2,
                       precision="bf16", grad_comm="reduce_scatter",
                       memory_budget_gib=2.5, lr=3e-4, total_steps=7)
    back = RunConfig.from_json(json.loads(json.dumps(config.to_json())))
    assert back.model == cfg
    assert back.plan == pl
    assert back == config


def test_plan_json_roundtrip_preserves_stages():
    cfg = _smoke()
    base = plan_lib.uniform_plan(cfg)
    pl = dataclasses.replace(
        base, precision="bf16", cost=1.25,
        stages=tuple(dataclasses.replace(s, remat=True)
                     for s in base.stages))
    assert plan_from_json(plan_to_json(pl)) == pl


def test_conv_config_json_restores_tuples():
    d = dataclasses.asdict(_smoke())
    back = conv_config_from_json(json.loads(json.dumps(d)))
    assert isinstance(back.conv_channels, tuple)
    assert back == _smoke()


# ----------------------------------------------------- session lifecycle ----
def test_session_matches_raw_assembly_path():
    """Session.step is the same program as the raw kwarg assembly (guard
    matched to the Session default): the trajectories agree bitwise on a
    single device."""
    import jax
    import jax.numpy as jnp

    from repro.models import cosmoflow
    from repro.optim.adam import Adam, linear_decay
    from repro.train.train_step import (make_convnet_opt_state,
                                        make_convnet_train_step)

    cfg = _smoke()
    gb = 2
    session = api_compile(RunConfig(model=cfg, global_batch=gb,
                                    total_steps=10))
    x, y = session._synthetic_batch()
    for _ in range(2):
        loss_s = session.step((x, y))

    opt = Adam(lr=linear_decay(1e-3, 10))
    step = make_convnet_train_step(cfg, session.mesh, opt, global_batch=gb,
                                   plan=session.plan, guard=True)
    p = cosmoflow.init_params(jax.random.PRNGKey(0), cfg)
    st = make_convnet_opt_state(cfg, opt, p, mesh=session.mesh,
                                plan=session.plan)
    for s in range(2):
        p, st, loss_r, _ = step(p, st, x, y, jnp.asarray(s, jnp.int32))
    assert float(loss_s) == float(loss_r)
    for k in p:
        assert np.array_equal(np.asarray(session.params[k]),
                              np.asarray(p[k])), k


def test_describe_reports_plan_memory_and_time():
    session = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                    memory_budget_gib=4.0))
    rep = session.describe()
    assert rep.plan_name == session.plan.name
    assert rep.mesh_shape == dict(session.mesh.shape)
    assert rep.modeled_peak.total > 0
    assert rep.predicted_step_s > 0
    assert rep.memory_budget_bytes == 4.0 * 2 ** 30
    assert rep.modeled_peak.total <= rep.memory_budget_bytes
    text = str(rep)
    assert rep.plan_name in text and "predicted step" in text


def test_make_loader_follows_plan_specs():
    from jax.sharding import PartitionSpec as P

    ucfg = configs.get_smoke_config("unet3d-256")
    session = api_compile(RunConfig(model=ucfg, global_batch=2))
    loader = session.make_loader(num_samples=4)
    assert loader.sharding.spec == P("data", "model", None, None, None)
    assert loader.label_sharding.spec == P("data", "model", None, None)
    x, yv = loader.load_batch(np.arange(2))
    assert x.shape[0] == 2 and yv.shape == x.shape[:-1]
    loss = session.step((x, yv))
    assert np.isfinite(float(loss))
    session.close()

    csession = api_compile(RunConfig(model=_smoke(), global_batch=2))
    closer = csession.make_loader(num_samples=4)
    assert closer.sharding.spec == P("data", "model", None, None, None)
    assert closer.label_sharding is None
    csession.close()


def test_save_embeds_restorable_config(tmp_path):
    ck = str(tmp_path / "ck")
    session = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                    checkpoint_dir=ck, total_steps=5))
    x, y = session._synthetic_batch()
    session.step((x, y))
    session.save()
    meta = json.load(open(os.path.join(ck, "run_config.json")))
    pinned = RunConfig.from_json(meta["run_config"])
    # every "auto" resolved: concrete model, plan, precision, grad_comm
    assert isinstance(pinned.plan, plan_lib.ParallelPlan)
    assert pinned.precision == "fp32"
    assert pinned.grad_comm == "overlap"
    restored = Session.restore(ck)
    assert restored.step_count == 1
    l_ref = session.step((x, y))
    l_res = restored.step((x, y))
    assert float(l_ref) == float(l_res)


def test_save_every_policy_autosaves(tmp_path):
    ck = str(tmp_path / "auto")
    session = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                    checkpoint_dir=ck, save_every=2,
                                    total_steps=5))
    x, y = session._synthetic_batch()
    session.step((x, y))
    assert not os.path.exists(os.path.join(ck, "manifest.json"))
    session.step((x, y))
    from repro.train import checkpoint
    assert checkpoint.latest_step(ck) == 2


# --------------------------------------------------------- driver hygiene ----
def test_drivers_assemble_only_via_api():
    """Acceptance: the examples contain zero direct calls to the
    internal assembly layer — repro.api.compile is the one path."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    drivers = [
        os.path.join(root, "examples", "quickstart.py"),
        os.path.join(root, "examples", "train_cosmoflow.py"),
        os.path.join(root, "examples", "train_unet3d.py"),
    ]
    forbidden = ("make_convnet_train_step", "make_convnet_opt_state",
                 "make_plan_mesh", "make_convnet_eval_step",
                 "make_convnet_phase_probes")
    for path in drivers:
        src = open(path).read()
        for name in forbidden:
            assert name not in src, f"{os.path.basename(path)} calls {name}"


# ----------------------------------------------- hybrid-mesh contracts ----
def test_session_parity_matrix_2data_x_2spatial(multidevice):
    """Acceptance: Session-driven training is step-parity (<=1e-5) with
    the legacy assembly for {cosmoflow, unet3d} x {overlap,
    reduce_scatter} x {fp32, bf16} on a 2-data x 2-spatial mesh."""
    multidevice("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.api import RunConfig, compile as api_compile
from repro.models import cosmoflow, unet3d
from repro.optim.adam import Adam, linear_decay
from repro.train.train_step import (make_convnet_opt_state,
                                    make_convnet_train_step)

ccfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                           input_width=16)
ucfg = configs.get_smoke_config('unet3d-256')
gb = 4
for cfg in (ccfg, ucfg):
    W = cfg.input_width
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (gb, W, W, W, cfg.in_channels))
    if cfg.arch == 'cosmoflow':
        y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
        init = cosmoflow.init_params
    else:
        y = jax.random.randint(jax.random.PRNGKey(1), (gb, W, W, W), 0,
                               cfg.out_dim)
        init = unet3d.init_params
    for gc in ('overlap', 'reduce_scatter'):
        for prec in ('fp32', 'bf16'):
            sess = api_compile(RunConfig(
                model=cfg, global_batch=gb, data=2, spatial=2,
                grad_comm=gc, precision=prec, total_steps=10))
            loss_s = sess.step((x, y))
            opt = Adam(lr=linear_decay(1e-3, 10))
            step = make_convnet_train_step(
                cfg, sess.mesh, opt, global_batch=gb, grad_comm=gc,
                plan=sess.plan, precision=prec)
            p = init(jax.random.PRNGKey(0), cfg)
            st = make_convnet_opt_state(cfg, opt, p, mesh=sess.mesh,
                                        grad_comm=gc, plan=sess.plan,
                                        precision=prec)
            p, st, loss_r = step(p, st, x, y, jnp.asarray(0, jnp.int32))
            assert abs(float(loss_s) - float(loss_r)) <= 1e-5, \\
                (cfg.arch, gc, prec, float(loss_s), float(loss_r))
            for a, b in zip(jax.tree.leaves(sess.params),
                            jax.tree.leaves(p)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            print('parity OK', cfg.arch, gc, prec)
print("OK")
""", devices=4, timeout=560)


def test_session_restore_bitwise_2data_x_2spatial(multidevice):
    """Acceptance satellite: save -> reconstruct from the manifest alone
    (config embedded in the checkpoint) -> bitwise-equal continued step,
    on a 2-data x 2-spatial mesh with ZeRO-1 sharded opt state."""
    multidevice("""
import dataclasses
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro import configs
from repro.api import RunConfig, Session, compile as api_compile

cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          input_width=16)
gb, W = 4, cfg.input_width
x = jax.random.normal(jax.random.PRNGKey(0), (gb, W, W, W, cfg.in_channels))
y = jax.random.normal(jax.random.PRNGKey(1), (gb, cfg.out_dim))
sess = api_compile(RunConfig(model=cfg, global_batch=gb, data=2, spatial=2,
                             grad_comm='reduce_scatter', total_steps=10))
for _ in range(2):
    sess.step((x, y))
m0 = jax.tree.leaves(sess.opt_state.m)[0]
assert isinstance(m0.sharding, NamedSharding)  # genuinely ZeRO-1 sharded

with tempfile.TemporaryDirectory() as d:
    sess.save(d + '/ck')
    for _ in range(2):
        sess.step((x, y))
    restored = Session.restore(d + '/ck')
    assert restored.step_count == 2
    assert restored.grad_comm == 'reduce_scatter'
    assert dict(restored.mesh.shape) == {'data': 2, 'model': 2}
    m_r = jax.tree.leaves(restored.opt_state.m)[0]
    assert isinstance(m_r.sharding, NamedSharding)
    assert not m_r.sharding.is_fully_replicated
    for _ in range(2):
        restored.step((x, y))
    for k in sess.params:
        assert np.array_equal(np.asarray(sess.params[k]),
                              np.asarray(restored.params[k])), k
    for a, b in zip(jax.tree.leaves(sess.opt_state),
                    jax.tree.leaves(restored.opt_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
print("OK")
""", devices=4, timeout=560)
