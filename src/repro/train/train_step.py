"""Train-step builders.

The paper's conv nets train through one distribution style: a
whole-model ``jax.shard_map`` with explicit halo collectives. Gradient
reduction follows the ``grad_comm`` mode (DESIGN.md §4): per-layer
bucketed reduction hooks that fire during backward (``overlap``,
default — the data-parallel allreduce of paper Fig. 2 fused with the
spatial-partition reduction and overlapped with backprop), the seed's
tail tree-wide psum (``monolithic``, equivalence oracle), or ZeRO-1
``psum_scatter`` + sharded optimizer + ``all_gather``
(``reduce_scatter``).

This is the INTERNAL assembly layer. Drivers (examples, the CLI,
bench e2e paths) go through ``repro.api.compile`` (DESIGN.md §10),
which owns the mesh/plan/precision/opt-state threading and lowers to
the builders here; calling ``make_convnet_train_step`` directly from a
driver is deprecated. Tests and benches still pin these builders
directly — they are the substrate the Session's parity is measured
against.
"""
from __future__ import annotations

import threading
import time
from concurrent import futures as _futures
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ConvNetConfig
from repro.core import compat, flags
from repro.core import grad_comm as grad_comm_lib
from repro.core import plan as plan_lib
from repro.core import precision as precision_lib
from repro.core import reshard as reshard_lib
from repro.core.spatial_conv import SpatialPartitioning
from repro.models import cosmoflow as cosmoflow_lib
from repro.models import unet3d as unet_lib
from repro.obs import trace as trace_lib
from repro.train import guard as guard_lib


# ----------------------------------------------------------- conv nets ----
def _resolve_grad_comm(grad_comm: Optional[str]) -> str:
    mode = grad_comm if grad_comm is not None else flags.get("grad_comm")
    if mode not in grad_comm_lib.MODES:
        raise ValueError(
            f"grad_comm={mode!r}; expected one of {grad_comm_lib.MODES}")
    return mode


def _convnet_param_shapes(cfg: ConvNetConfig):
    init_fn = (cosmoflow_lib.init_params if cfg.arch == "cosmoflow"
               else unet_lib.init_params)
    return jax.eval_shape(lambda k: init_fn(k, cfg), jax.random.PRNGKey(0))


def convnet_grad_plan(cfg: ConvNetConfig) -> "grad_comm_lib.Plan":
    """The bucket plan the conv-net step uses for ``cfg`` — derived from
    the init-param shapes under the CURRENT bucket policy. Opt-state
    construction and step building must agree on it, so a
    ``grad_comm.bucket_policy(...)`` override has to wrap both (or pass
    an explicit ``bucket_plan=`` to ``make_convnet_opt_state``)."""
    return grad_comm_lib.make_plan(_convnet_param_shapes(cfg))


def make_convnet_opt_state(
    cfg: ConvNetConfig,
    optimizer,
    params,
    *,
    mesh=None,
    data_axes: Tuple[str, ...] = ("data",),
    grad_comm: Optional[str] = None,
    plan: Optional["plan_lib.ParallelPlan"] = None,
    bucket_plan=None,
    precision=None,
):
    """Optimizer state matching ``make_convnet_train_step``'s mode:
    replicated full-tree state for monolithic/overlap, ZeRO-1 flat bucket
    state (dim 0 sharded over the data axes by the step's specs) for
    reduce_scatter (which requires ``mesh``).

    ``precision`` must match the step's policy: fp16 wraps the state in
    the loss-scale machine (``core/precision.py``), fp32/bf16 leave it
    untouched. Like the step builder, it defaults to ``plan``'s recorded
    policy — pass the same ``ParallelPlan`` you hand the step and a
    precision-carrying (budgeted) plan stays self-consistent.
    ``bucket_plan`` overrides the §4 gradient bucket plan for the ZeRO-1
    state layout."""
    mode = _resolve_grad_comm(grad_comm)
    if precision is None and plan is not None:
        precision = plan.precision
    optimizer = precision_lib.wrap_optimizer(optimizer, precision)
    if mode != "reduce_scatter":
        return optimizer.init(params)
    if mesh is None:
        raise ValueError("grad_comm='reduce_scatter' opt state is sharded "
                         "over the data axes: pass mesh=")
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    return grad_comm_lib.init_sharded_opt_state(
        optimizer,
        bucket_plan if bucket_plan is not None else convnet_grad_plan(cfg),
        num_shards=n_data)


def resolve_convnet_plan(
    cfg: ConvNetConfig,
    mesh,
    *,
    spatial_axes: Tuple[Optional[str], ...] = ("model", None, None),
    data_axes: Tuple[str, ...] = ("data",),
    plan: Optional["plan_lib.ParallelPlan"] = None,
) -> "plan_lib.ParallelPlan":
    """The plan a conv-net step will execute: the caller's, or the legacy
    fixed-degree plan (with its over-decomposition gathers and replicated
    FC head) derived from ``spatial_axes`` + the mesh degrees.

    A caller-supplied plan is validated against the mesh: every axis the
    plan references must exist with the plan's recorded degree — the
    degrees feed ``loss_redundancy``, so a silent mismatch would scale
    the loss (and every gradient) by the wrong factor."""
    if plan is not None:
        for a in plan.axis_names:
            if a not in mesh.shape:
                raise ValueError(
                    f"plan {plan.name!r} references axis {a!r} missing "
                    f"from mesh {dict(mesh.shape)}")
            if plan.degree(a) != mesh.shape[a]:
                raise ValueError(
                    f"plan {plan.name!r} records {a!r} degree "
                    f"{plan.degree(a)} but the mesh has {mesh.shape[a]}")
        return plan
    shards3 = tuple(mesh.shape[a] if a else 1 for a in spatial_axes)
    return plan_lib.legacy_convnet_plan(
        cfg, SpatialPartitioning(tuple(spatial_axes)), shards3,
        data_axes=tuple(data_axes),
        data_degrees=tuple(mesh.shape[a] for a in data_axes))


def _build_convnet_step(
    cfg: ConvNetConfig,
    mesh,
    optimizer,
    *,
    spatial_axes: Tuple[Optional[str], ...],
    data_axes: Tuple[str, ...],
    global_batch: int,
    use_pallas: bool,
    overlap: Optional[bool],
    grad_comm: Optional[str],
    stage: str,  # "fwd" | "bwd" | "grad_comm" | "step"
    plan: Optional["plan_lib.ParallelPlan"] = None,
    precision=None,  # None -> the plan's policy (DESIGN.md §9)
    guard: bool = False,  # psum-agreed skip of non-finite steps (§11)
):
    """Common builder for the train step and its phase probes.

    Stages nest: ``fwd`` returns the loss only; ``bwd`` adds the backward
    pass with NO gradient reduction; ``grad_comm`` adds the mode's
    reduction (returning the reduced grad tree); ``step`` adds the
    optimizer update. Successive timing differences attribute the e2e
    cost to fwd / bwd / grad-comm / optimizer (benchmarks/run.py).

    ``plan`` selects the per-stage parallelism plan (DESIGN.md §5); the
    default is the legacy fixed-degree plan over ``spatial_axes``. A plan
    overrides ``spatial_axes``/``data_axes`` with its first stage's layout
    (inputs are sharded for stage 0; later stages reshard in-graph).

    ``precision`` (default: the plan's recorded policy) drives the §9
    mixed-precision lowering: params are kept as fp32 masters and cast
    per step inside the model, a scaling policy multiplies the LOCAL loss
    by the running loss scale before ``value_and_grad`` (every device
    applies the same scale, so psums stay correct) and hands the scale to
    the optimizer to unscale before clipping; non-finite fp16 grads skip
    the step inside the wrapped optimizer. The fp32 path is bit-identical
    to the pre-precision lowering.

    ``guard`` (``step`` stage only, DESIGN.md §11) adds psum-agreed
    non-finite loss/grad detection for EVERY precision: a bad step holds
    params and optimizer state bitwise (fp16 routes the verdict through
    its own §9 skip machine so the loss scale still backs off), and the
    step returns a fourth output — 1.0 if the update applied, 0.0 if it
    was skipped — for host-side telemetry. With finite values the
    guarded step is value-transparent (bitwise-equal trajectory).
    """
    mode = _resolve_grad_comm(grad_comm)
    plan = resolve_convnet_plan(cfg, mesh, spatial_axes=spatial_axes,
                                data_axes=data_axes, plan=plan)
    policy = precision_lib.get(
        precision if precision is not None else plan.precision)
    optimizer = precision_lib.wrap_optimizer(optimizer, policy)
    entry = plan.stages[0]
    spatial_axes = tuple(entry.spatial_axes)
    data_axes = tuple(entry.batch_axes)
    spatial_names = plan.spatial_axis_names
    all_axes = plan.axis_names
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]

    # DESIGN.md §4: where each mode reduces. "overlap" hooks the full
    # fused (data+spatial) psum into backward; "reduce_scatter" hooks the
    # spatial reduction only (the data-axis reduction becomes the bucket
    # psum_scatter); "monolithic" reduces nothing in backward.
    if stage in ("fwd", "bwd"):
        model_grad_axes: Tuple[str, ...] = ()
    elif mode == "overlap":
        model_grad_axes = all_axes
    elif mode == "reduce_scatter":
        model_grad_axes = spatial_names
    else:
        model_grad_axes = ()

    bucket_plan = (convnet_grad_plan(cfg) if mode == "reduce_scatter"
                   else None)

    def local_step(params, opt_state, x, y, seed):
        # §14 trace-time marker: this host code runs once per jit trace,
        # not per step — the instant records WHICH program (fwd / bwd /
        # grad_comm / step, and its reduction mode) was traced and when;
        # the in-graph phases themselves are attributed by the probes.
        trace_lib.instant("trace.convnet_step", stage=stage, mode=mode,
                          arch=cfg.arch)
        # dropout rng is NOT folded per-device: masks are derived per global
        # sample id so the redundant FC compute on every spatial shard sees
        # identical masks and results are mesh-shape invariant.
        rng = jax.random.PRNGKey(seed)
        n_loc = x.shape[0]
        data_idx = (lax.axis_index(data_axes) if len(data_axes) > 1 or
                    mesh.shape[data_axes[0]] > 1 else 0)
        sample_ids = data_idx * n_loc + jnp.arange(n_loc)

        if cfg.arch == "cosmoflow":
            def loss_fn(p):
                return cosmoflow_lib.mse_loss(
                    p, x, y, cfg, plan=plan, bn_axes=all_axes,
                    global_batch=global_batch, sample_ids=sample_ids,
                    train=True, dropout_rng=rng, use_pallas=use_pallas,
                    overlap=overlap, grad_axes=model_grad_axes,
                    precision=policy)
        else:
            gv = global_batch * cfg.input_width ** 3

            def loss_fn(p):
                return unet_lib.segmentation_loss(
                    p, x, y, cfg, plan=plan, bn_axes=all_axes,
                    global_voxels=gv, use_pallas=use_pallas,
                    overlap=overlap, grad_axes=model_grad_axes,
                    precision=policy)

        if stage == "fwd":
            return lax.psum(loss_fn(params), all_axes)

        if policy.uses_scaling:
            # fp16: scale the LOCAL loss so small cotangents survive the
            # narrow exponent range; identical on every device, so the
            # hook psums reduce consistently. Unscaled before reporting;
            # the optimizer unscales the grads before clipping.
            scale = precision_lib.current_scale(opt_state, policy)
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p) * scale)(params)
            with jax.named_scope("loss"):
                loss = lax.psum(loss / scale, all_axes)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params)
            with jax.named_scope("loss"):
                loss = lax.psum(loss, all_axes)
        if stage == "bwd":
            # timing-only probe: collapse the (per-device partial) grads
            # into one psummed scalar — forces the full backward without
            # presenting unreduced trees as replicated output, and
            # without the per-leaf reduction this stage exists to exclude
            gsum = sum(jnp.sum(g) for g in jax.tree.leaves(grads))
            return loss, lax.psum(gsum, all_axes)

        if mode == "monolithic":
            with jax.named_scope("grad_comm"):
                grads = jax.tree.map(lambda g: lax.psum(g, all_axes), grads)
        if stage == "grad_comm":
            if mode == "reduce_scatter":
                # pure-comm probe: scatter + gather, no optimizer math
                shards = grad_comm_lib.reduce_scatter_grads(
                    grads, bucket_plan, data_axes)
                grads = grad_comm_lib.all_gather_params(
                    shards, bucket_plan, data_axes, grads)
            return loss, grads

        with jax.named_scope("optimizer"):
            applied = None
            if guard:
                # §11: one agreed verdict BEFORE the update. fp16 hands
                # the loss-veto to its own skip machine (poisoned grads)
                # so the scale still backs off; fp32/bf16 select after
                # the update.
                applied = guard_lib.agreed_finite(loss, grads, all_axes)
                if policy.uses_scaling:
                    grads = guard_lib.poison_unless(applied, grads)
            if mode == "reduce_scatter":
                new_params, new_opt = grad_comm_lib.sharded_update(
                    optimizer, grads, opt_state, params, bucket_plan,
                    data_axes)
            else:
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
            if guard:
                if not policy.uses_scaling:
                    new_params = guard_lib.tree_select(applied, new_params,
                                                      params)
                    new_opt = guard_lib.tree_select(applied, new_opt,
                                                   opt_state)
                return (new_params, new_opt, loss,
                        applied.astype(jnp.float32))
        return new_params, new_opt, loss

    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    x_spec = P(dspec, *spatial_axes, None)
    y_spec = (P(dspec, *spatial_axes) if cfg.arch == "unet3d"
              else P(dspec, None))
    opt_spec: Any = P()
    if mode == "reduce_scatter":
        # per-bucket flat vectors, dim 0 sharded over the data axes (the
        # ZeRO-1 memory win); scalars (step count) replicated.
        state_shapes = jax.eval_shape(
            lambda: grad_comm_lib.init_sharded_opt_state(
                optimizer, bucket_plan, num_shards=n_data))
        shard_spec = P(tuple(data_axes))
        opt_spec = jax.tree.map(
            lambda s: P() if s.ndim == 0 else shard_spec, state_shapes)
    out_specs = {
        "fwd": P(),
        "bwd": (P(), P()),
        "grad_comm": (P(), P()),
        "step": ((P(), opt_spec, P(), P()) if guard
                 else (P(), opt_spec, P())),
    }[stage]
    return compat.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), opt_spec, x_spec, y_spec, P()),
        out_specs=out_specs,
    )


def make_convnet_train_step(
    cfg: ConvNetConfig,
    mesh,
    optimizer,
    *,
    spatial_axes: Tuple[Optional[str], ...] = ("model", None, None),
    data_axes: Tuple[str, ...] = ("data",),
    global_batch: int,
    use_pallas: bool = False,
    overlap: Optional[bool] = None,  # halo mode: None -> flags overlap_halo
    grad_comm: Optional[str] = None,  # None -> flags grad_comm
    plan: Optional["plan_lib.ParallelPlan"] = None,  # DESIGN.md §5
    precision=None,  # None -> the plan's policy (DESIGN.md §9)
    guard: bool = False,  # §11 non-finite step guard (+applied output)
    jit: bool = True,
):
    """Returns step(params, opt_state, x, y, rng) -> (params, opt, loss).

    x: (N, D, H, W, C) sharded for the plan's first stage (data...,
    spatial...); y: (N, out) or voxel labels (N, D, H, W) for unet.
    ``grad_comm="reduce_scatter"`` steps expect ``opt_state`` from
    ``make_convnet_opt_state`` (flat ZeRO-1 bucket state); the other
    modes take ``optimizer.init(params)``. ``plan`` selects a per-stage
    parallelism plan and overrides ``spatial_axes``/``data_axes``.
    ``precision`` selects the mixed-precision policy; ``params`` are
    always the fp32 masters (``make_convnet_opt_state`` must be built
    with the same policy so fp16 state carries the loss-scale machine).
    ``guard=True`` returns ``(params, opt, loss, applied)`` — see
    ``_build_convnet_step``.
    """
    mapped = _build_convnet_step(
        cfg, mesh, optimizer, spatial_axes=spatial_axes,
        data_axes=data_axes, global_batch=global_batch,
        use_pallas=use_pallas, overlap=overlap, grad_comm=grad_comm,
        stage="step", plan=plan, precision=precision, guard=guard)
    if not jit:
        return mapped
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_convnet_phase_probes(
    cfg: ConvNetConfig,
    mesh,
    optimizer,
    *,
    spatial_axes: Tuple[Optional[str], ...] = ("model", None, None),
    data_axes: Tuple[str, ...] = ("data",),
    global_batch: int,
    use_pallas: bool = False,
    overlap: Optional[bool] = None,
    grad_comm: Optional[str] = None,
    plan: Optional["plan_lib.ParallelPlan"] = None,
    precision=None,
) -> Dict[str, Callable]:
    """Jitted probes isolating the train-step phases for attribution:
    ``fwd`` (loss only), ``bwd`` (+backward, no reduction), ``grad_comm``
    (+the mode's reduction), ``step`` (full). All share the step's
    signature (non-``step`` probes ignore ``opt_state``); phase times are
    successive differences. No donation — the bench re-times one input.
    """
    return {
        stage: jax.jit(_build_convnet_step(
            cfg, mesh, optimizer, spatial_axes=spatial_axes,
            data_axes=data_axes, global_batch=global_batch,
            use_pallas=use_pallas, overlap=overlap, grad_comm=grad_comm,
            stage=stage, plan=plan, precision=precision))
        for stage in ("fwd", "bwd", "grad_comm", "step")
    }


def make_convnet_eval_step(
    cfg: ConvNetConfig,
    mesh,
    *,
    spatial_axes: Tuple[Optional[str], ...] = ("model", None, None),
    data_axes: Tuple[str, ...] = ("data",),
    global_batch: int,
    use_pallas: bool = False,
    overlap: Optional[bool] = None,
    plan: Optional["plan_lib.ParallelPlan"] = None,
    precision=None,
):
    """Returns eval(params, x, y) -> (loss, preds).

    CosmoFlow: the regression MSE and per-sample predictions — under a
    plan whose CNN->FC transition repartitions the spatial group into
    the batch, ``preds`` comes back sharded over the FC stage's batch
    axes (each sample computed exactly once). U-Net: the voxel
    cross-entropy (same ops as ``segmentation_loss``, so the loss is
    bitwise-equal to the fwd probe's) and the per-voxel logits in the
    plan's level-0 layout."""
    plan = resolve_convnet_plan(cfg, mesh, spatial_axes=spatial_axes,
                                data_axes=data_axes, plan=plan)
    entry = plan.stages[0]
    spatial_axes = tuple(entry.spatial_axes)
    data_axes = tuple(entry.batch_axes)
    all_axes = plan.axis_names
    redundancy = plan.loss_redundancy
    fc_batch = plan.final_stage.batch_axes

    def local_eval(params, x, y):
        if cfg.arch == "cosmoflow":
            pred = cosmoflow_lib.forward(
                params, x, cfg, plan=plan, bn_axes=all_axes, train=False,
                use_pallas=use_pallas, overlap=overlap, precision=precision)
            y = reshard_lib.shard_batch(y, plan.batch_extension_axes)
            per = jnp.mean(jnp.square(pred.astype(jnp.float32) - y),
                           axis=-1)
            loss = lax.psum(jnp.sum(per) / (global_batch * redundancy),
                            all_axes)
            return loss, pred
        logits = unet_lib.forward(
            params, x, cfg, plan=plan, bn_axes=all_axes,
            use_pallas=use_pallas, overlap=overlap, precision=precision)
        # exactly segmentation_loss's ops on the same logits, so the
        # returned loss matches the fwd probe bitwise
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        gv = global_batch * cfg.input_width ** 3
        loss = lax.psum(jnp.sum(nll) / gv, all_axes)
        return loss, logits

    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    fc_dspec = fc_batch if len(fc_batch) > 1 else fc_batch[0]
    x_spec = P(dspec, *spatial_axes, None)
    if cfg.arch == "cosmoflow":
        y_spec, pred_spec = P(dspec, None), P(fc_dspec, None)
    else:
        # labels and logits both live in the level-0 spatial layout
        y_spec = P(dspec, *spatial_axes)
        pred_spec = P(dspec, *spatial_axes, None)
    return jax.jit(compat.shard_map(
        local_eval, mesh=mesh,
        in_specs=(P(), x_spec, y_spec),
        out_specs=(P(), pred_spec),
    ))


def make_convnet_forward_step(
    cfg: ConvNetConfig,
    mesh,
    *,
    spatial_axes: Tuple[Optional[str], ...] = ("model", None, None),
    data_axes: Tuple[str, ...] = ("data",),
    use_pallas: bool = False,
    overlap: Optional[bool] = None,
    plan: Optional["plan_lib.ParallelPlan"] = None,
    precision=None,
):
    """Returns fwd(params, x) -> preds: the serving forward (§15).

    The same plan-sharded forward the eval step runs — overlapped-halo
    conv (§3) and in-graph resharding (§5) included — but with no loss
    term. The input is not donated: no output has its shape, so XLA
    could not reuse its buffer. CosmoFlow returns (B, out_dim)
    predictions (sharded over the FC stage's batch axes); the U-Net
    returns per-voxel logits in the plan's level-0 layout."""
    plan = resolve_convnet_plan(cfg, mesh, spatial_axes=spatial_axes,
                                data_axes=data_axes, plan=plan)
    entry = plan.stages[0]
    spatial_axes = tuple(entry.spatial_axes)
    data_axes = tuple(entry.batch_axes)
    all_axes = plan.axis_names
    fc_batch = plan.final_stage.batch_axes

    def local_fwd(params, x):
        if cfg.arch == "cosmoflow":
            return cosmoflow_lib.forward(
                params, x, cfg, plan=plan, bn_axes=all_axes, train=False,
                use_pallas=use_pallas, overlap=overlap, precision=precision)
        return unet_lib.forward(
            params, x, cfg, plan=plan, bn_axes=all_axes,
            use_pallas=use_pallas, overlap=overlap, precision=precision)

    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    fc_dspec = fc_batch if len(fc_batch) > 1 else fc_batch[0]
    x_spec = P(dspec, *spatial_axes, None)
    out_spec = (P(fc_dspec, None) if cfg.arch == "cosmoflow"
                else P(dspec, *spatial_axes, None))
    fn = compat.shard_map(local_fwd, mesh=mesh, in_specs=(P(), x_spec),
                          out_specs=out_spec)
    return jax.jit(fn)


# ------------------------------------------------- pipeline groups (§13) --
def pipeline_group_params(cfg: ConvNetConfig, plan: "plan_lib.ParallelPlan",
                          params) -> Tuple[dict, ...]:
    """Split the full param dict into per-group subsets: group ``g`` owns
    exactly the params its plan layers ``group_layer_ranges()[g]`` consume
    (``segment_param_names``). The subsets are disjoint and cover the
    tree, so ``dict`` union of the groups reconstructs ``params``."""
    seg = (cosmoflow_lib.segment_param_names if cfg.arch == "cosmoflow"
           else unet_lib.segment_param_names)
    return tuple({k: params[k] for k in seg(cfg, a, b)}
                 for a, b in plan.group_layer_ranges())


def make_pipeline_opt_state(
    cfg: ConvNetConfig,
    optimizer,
    params,
    *,
    plan: "plan_lib.ParallelPlan",
    meshes=None,
    precision=None,
):
    """Per-group optimizer state for ``make_pipeline_train_step``: a tuple
    of ``optimizer.init`` over each group's param subset, placed
    (replicated) on the group's mesh when ``meshes`` is given. fp16 is
    rejected like the step — the §9 loss-scale machine assumes one
    shard_map over the whole tree."""
    policy = precision_lib.get(
        precision if precision is not None else plan.precision)
    if policy.uses_scaling:
        raise ValueError("fp16 loss scaling is not supported under "
                         "pipeline groups; use fp32 or bf16")
    optimizer = precision_lib.wrap_optimizer(optimizer, policy)
    groups = pipeline_group_params(cfg, plan, params)
    if meshes is not None:
        groups = tuple(
            reshard_lib.to_group(g, NamedSharding(m, P()))
            for g, m in zip(groups, meshes))
    return tuple(optimizer.init(g) for g in groups)


def _schedule_order(K: int, M: int, schedule: str):
    """Host dispatch order for a K-node forward chain over M micro-batches.

    ``sequential`` is the GPipe-naive oracle: per micro-batch, the whole
    forward chain then the whole backward chain, with a ``SYNC`` marker
    (the engine blocks on that micro-batch's loss) so nothing overlaps —
    the equivalence baseline the 1F1B speedup is measured against.

    ``1f1b`` emits the canonical one-forward-one-backward order: node k
    ramps up with ``min(K-1-k, M)`` warmup forwards, then alternates
    forward/backward until its micro-batches drain. The forward comes
    FIRST in each steady-state pair (the canonical 1F1B order): the
    node enqueues the next micro-batch's forward before its dispatcher
    blocks on the downstream cotangent, keeping ``K-k`` micro-batches
    in flight — backward-first would collapse the window to one and
    serialize the whole schedule through every stage boundary. The per-node streams
    are merged by a dependency scan (F_k(m) after F_{k-1}(m); B_k(m)
    after B_{k+1}(m); the last node's fused FB after F_{K-2}(m)), which
    yields a topologically valid enqueue order. Correctness never depends
    on the order — JAX tracks data dependencies — only the device-queue
    interleaving (and therefore the bubble) does."""
    if schedule == "sequential":
        out = []
        for m in range(M):
            out += [("F", k, m) for k in range(K - 1)]
            out.append(("FB", K - 1, m))
            out += [("B", k, m) for k in range(K - 2, -1, -1)]
            out.append(("SYNC", -1, m))
        return out
    per = []
    for k in range(K - 1):
        warm = min(K - 1 - k, M)
        seq = [("F", k, m) for m in range(warm)]
        f_next = warm
        for b in range(M):
            if f_next < M:
                seq.append(("F", k, f_next))
                f_next += 1
            seq.append(("B", k, b))
        per.append(seq)
    per.append([("FB", K - 1, m) for m in range(M)])
    done, order, pos = set(), [], [0] * K
    total = sum(len(s) for s in per)
    while len(order) < total:
        progressed = False
        for k in range(K):
            while pos[k] < len(per[k]):
                op, _, m = per[k][pos[k]]
                if op == "F" and k > 0 and ("F", k - 1, m) not in done:
                    break
                if op == "FB" and ("F", k - 1, m) not in done:
                    break
                if op == "B" and ("B", k + 1, m) not in done \
                        and ("FB", k + 1, m) not in done:
                    break
                done.add((op, k, m))
                order.append((op, k, m))
                pos[k] += 1
                progressed = True
        if not progressed:  # pragma: no cover — schedule invariant
            raise RuntimeError("1F1B dependency scan deadlocked")
    return order


class _Slots:
    """Thread-safe one-shot handoff slots for cross-group schedule edges.

    Producers ``set(key, value)`` exactly once; consumers ``take(key)``
    exactly once, blocking until the value arrives. The value may itself
    be a ``Future`` (an in-flight emulated-link transfer) — ``take``
    resolves it. ``fail(exc)`` poisons every outstanding and future slot
    so a dead dispatcher thread wakes its peers instead of deadlocking
    them."""

    def __init__(self):
        self._d: Dict[Any, _futures.Future] = {}
        self._lk = threading.Lock()
        self._exc: Optional[BaseException] = None

    def _fut(self, key) -> _futures.Future:
        with self._lk:
            if self._exc is not None:
                f = _futures.Future()
                f.set_exception(self._exc)
                return f
            f = self._d.get(key)
            if f is None:
                f = self._d[key] = _futures.Future()
            return f

    def set(self, key, val) -> None:
        self._fut(key).set_result(val)

    def take(self, key):
        v = self._fut(key).result()
        if isinstance(v, _futures.Future):
            v = v.result()
        with self._lk:
            self._d.pop(key, None)
        return v

    def fail(self, exc: BaseException) -> None:
        with self._lk:
            self._exc = exc
            for f in self._d.values():
                if not f.done():
                    f.set_exception(exc)


def make_pipeline_train_step(
    cfg: ConvNetConfig,
    meshes,
    optimizer,
    *,
    plan: "plan_lib.ParallelPlan",
    global_batch: int,
    grad_comm: Optional[str] = None,
    precision=None,
    guard: bool = False,
    schedule: Optional[str] = None,
    donate: bool = True,
):
    """Host-orchestrated pipelined train step (DESIGN.md §13).

    Returns ``step(params, opt_states, x, y, seed)`` ->
    ``(params, opt_states, loss[, applied])``. ``params`` is the FULL
    param dict (leaves live on their owning group's mesh); ``opt_states``
    is ``make_pipeline_opt_state``'s per-group tuple; ``x``/``y`` are the
    global batch on host (sliced into micro-batches here). The returned
    step is a Python function running one DISPATCHER THREAD PER GROUP:
    each thread consumes its group's slice of ``_schedule_order`` and
    enqueues that group's jitted ``shard_map`` nodes; cross-group
    boundary values (activation forward, cotangent backward) travel as
    futures (``_Slots``) resolved by a link pool that applies
    ``flags.pipeline_link_latency_s`` before ``reshard.cross_group``
    places them on the destination mesh. Under ``1f1b`` each thread
    keeps its warmup window of forwards in flight ahead of the
    backwards, so groups overlap; ``sequential`` blocks on every
    micro-batch's loss (a host SYNC) — the drained GPipe-naive oracle.
    Threads only change enqueue order, never values, so the two
    schedules are bitwise-equal.

    The backward of every non-loss node recomputes its segment forward
    under ``jax.vjp`` (activations between boundaries are never stored
    across micro-batches — only each node's INPUT is). Gradient reduction
    stays the §4 contract *within each group*: ``overlap`` hooks bucketed
    psums into the segment backward, ``monolithic`` reduces the segment
    tree at its tail; ``reduce_scatter`` is rejected (ZeRO-1 shards one
    tree over one mesh). Per-micro-batch grads accumulate on-device; the
    per-group optimizer updates run after the drain. ``guard`` (§11)
    computes one finiteness flag per group, exchanges the scalars across
    groups inside the update jits (no host sync), and holds every group
    bitwise unless all agree.

    Equivalence contract: the local loss is ``sum(per_sample)/global``
    per micro-batch, so micro-batch losses and grads SUM to the
    no-pipeline full-batch values; dropout masks are keyed by global row
    id (``m*mb`` offset + group-local index) and match the no-pipeline
    masks bit for bit. BatchNorm stats span one micro-batch — identical
    between the two schedules at any M, and equal to the no-pipeline
    stats when ``micro_batches == 1``.

    ``schedule`` overrides the plan's recorded schedule (benches time
    both from one plan)."""
    mode = _resolve_grad_comm(grad_comm)
    if mode == "reduce_scatter":
        raise ValueError(
            "grad_comm='reduce_scatter' does not compose with pipeline "
            "groups (ZeRO-1 shards the full tree over one mesh); use "
            "'overlap' or 'monolithic'")
    spec = plan.pipeline
    n_grp = plan.n_groups
    if spec is None or n_grp < 2:
        raise ValueError(f"plan {plan.name!r} has no pipeline axis; use "
                         "make_convnet_train_step")
    if len(meshes) != n_grp:
        raise ValueError(f"plan {plan.name!r} has {n_grp} groups but "
                         f"{len(meshes)} meshes were given")
    policy = precision_lib.get(
        precision if precision is not None else plan.precision)
    if policy.uses_scaling:
        raise ValueError("fp16 loss scaling is not supported under "
                         "pipeline groups; use fp32 or bf16")
    if getattr(optimizer, "grad_clip", 0.0):
        raise ValueError("grad_clip needs the global grad norm across "
                         "groups; set grad_clip=0 under pipelined plans")
    optimizer = precision_lib.wrap_optimizer(optimizer, policy)
    sched = schedule if schedule is not None else spec.schedule
    if sched not in plan_lib.PIPELINE_SCHEDULES:
        raise ValueError(f"schedule={sched!r}; expected one of "
                         f"{plan_lib.PIPELINE_SCHEDULES}")
    M = spec.micro_batches
    if global_batch % M:
        raise ValueError(f"global_batch={global_batch} not divisible by "
                         f"micro_batches={M}")
    mb = global_batch // M
    d = plan.data_degree
    if mb % d:
        raise ValueError(f"micro-batch {mb} not divisible by the per-group "
                         f"data degree {d}")
    axes = plan.axis_names          # the per-group axes (batch only)
    gx = axes if mode == "overlap" else ()
    ranges = plan.group_layer_ranges()
    rep = tuple(NamedSharding(m, P()) for m in meshes)
    bat = tuple(reshard_lib.group_sharding(m, axes) for m in meshes)
    dspec = axes if len(axes) > 1 else axes[0]
    bspec = P(dspec)

    def _psum_tree(t):
        return jax.tree.map(lambda g: lax.psum(g, axes), t)

    def _smap(f, g, in_specs, out_specs):
        return jax.jit(compat.shard_map(
            f, mesh=meshes[g], in_specs=in_specs, out_specs=out_specs))

    # ---- the forward node chain: cosmoflow is one segment per group; the
    # U-Net V-cycle visits each group twice (down on descent, up on
    # ascent), so its chain is down_0..down_{P-2}, core_{P-1} (descent +
    # ascent of the deepest group, bottleneck included), up_{P-2}..up_1,
    # and up_0 fused with the loss. Skips never cross groups: a down
    # node's skips stay resident on its group until its up/backward visit.
    nodes = []
    if cfg.arch == "cosmoflow":
        for g, (a, b) in enumerate(ranges):
            if g < n_grp - 1:
                def f_loc(p, h, _a=a, _b=b):
                    return cosmoflow_lib.forward_range(
                        p, h, cfg, _a, _b, bn_axes=axes, train=True,
                        precision=policy)

                def b_loc(p, h, gout, _a=a, _b=b):
                    def f(p_, h_):
                        return cosmoflow_lib.forward_range(
                            p_, h_, cfg, _a, _b, bn_axes=axes, train=True,
                            grad_axes=gx, precision=policy)
                    _, vjp = jax.vjp(f, p, h)
                    gp, gh = vjp(gout)
                    if mode == "monolithic":
                        gp = _psum_tree(gp)
                    return gp, gh

                nodes.append(dict(
                    kind="seg", group=g, partner=None,
                    fwd=_smap(f_loc, g, (P(), bspec), bspec),
                    bwd=_smap(b_loc, g, (P(), bspec, bspec),
                              (P(), bspec))))
            else:
                def fb_loc(p, h, y, seed, off, _a=a, _b=b):
                    rng = jax.random.PRNGKey(seed)
                    n_loc = h.shape[0]
                    idx = (lax.axis_index(axes)
                           if len(axes) > 1 or d > 1 else 0)
                    ids = off + idx * n_loc + jnp.arange(n_loc)

                    def lf(p_, h_):
                        pred = cosmoflow_lib.forward_range(
                            p_, h_, cfg, _a, _b, bn_axes=axes, train=True,
                            dropout_rng=rng, sample_ids=ids, grad_axes=gx,
                            precision=policy)
                        per = jnp.mean(
                            jnp.square(pred.astype(jnp.float32) - y),
                            axis=-1)
                        return jnp.sum(per) / global_batch

                    loss, (gp, gh) = jax.value_and_grad(
                        lf, argnums=(0, 1))(p, h)
                    loss = lax.psum(loss, axes)
                    if mode == "monolithic":
                        gp = _psum_tree(gp)
                    return loss, gp, gh

                nodes.append(dict(
                    kind="loss", group=g, partner=None,
                    fused=_smap(fb_loc, g,
                                (P(), bspec, bspec, P(), P()),
                                (P(), P(), bspec))))
        loss_group = n_grp - 1
    else:
        gv = global_batch * cfg.input_width ** 3

        def _down_node(g, a, b, core):
            dn = unet_lib.down_param_names(cfg, a, b)
            up = unet_lib.up_param_names(cfg, a, b)
            n_sk = min(b, cfg.depth) - a

            def f_core(p, h, _a=a, _b=b):
                h2, sk = unet_lib.down_range(
                    {k: p[k] for k in dn}, h, cfg, _a, _b, bn_axes=axes,
                    precision=policy)
                return unet_lib.up_range(
                    {k: p[k] for k in up}, h2, sk, cfg, _a, _b,
                    bn_axes=axes, precision=policy)

            def f_down(p, h, _a=a, _b=b):
                return unet_lib.down_range(
                    p, h, cfg, _a, _b, bn_axes=axes, precision=policy)

            def b_core(p, h, gout):
                def f(p_, h_):
                    h2, sk = unet_lib.down_range(
                        {k: p_[k] for k in dn}, h_, cfg, a, b,
                        bn_axes=axes, grad_axes=gx, precision=policy)
                    return unet_lib.up_range(
                        {k: p_[k] for k in up}, h2, sk, cfg, a, b,
                        bn_axes=axes, grad_axes=gx, precision=policy)
                _, vjp = jax.vjp(f, p, h)
                gp, gh = vjp(gout)
                if mode == "monolithic":
                    gp = _psum_tree(gp)
                return gp, gh

            def b_down(p, h, gout, gsk):
                def f(p_, h_):
                    return unet_lib.down_range(
                        p_, h_, cfg, a, b, bn_axes=axes, grad_axes=gx,
                        precision=policy)
                _, vjp = jax.vjp(f, p, h)
                gp, gh = vjp((gout, gsk))
                if mode == "monolithic":
                    gp = _psum_tree(gp)
                return gp, gh

            if core:
                return dict(
                    kind="core", group=g, partner=None,
                    fwd=_smap(f_core, g, (P(), bspec), bspec),
                    bwd=_smap(b_core, g, (P(), bspec, bspec),
                              (P(), bspec)))
            sk_spec = (bspec,) * n_sk
            return dict(
                kind="down", group=g, partner=None,
                fwd=_smap(f_down, g, (P(), bspec), (bspec, sk_spec)),
                bwd=_smap(b_down, g, (P(), bspec, bspec, sk_spec),
                          (P(), bspec)))

        def _up_node(g, a, b, partner):
            n_sk = min(b, cfg.depth) - a
            sk_spec = (bspec,) * n_sk

            def f_up(p, h, sk, _a=a, _b=b):
                return unet_lib.up_range(
                    p, h, sk, cfg, _a, _b, bn_axes=axes, precision=policy)

            if g > 0:
                def b_up(p, h, sk, gout):
                    def f(p_, h_, s_):
                        return unet_lib.up_range(
                            p_, h_, s_, cfg, a, b, bn_axes=axes,
                            grad_axes=gx, precision=policy)
                    _, vjp = jax.vjp(f, p, h, sk)
                    gp, gh, gsk = vjp(gout)
                    if mode == "monolithic":
                        gp = _psum_tree(gp)
                    return gp, gh, gsk

                return dict(
                    kind="up", group=g, partner=partner,
                    fwd=_smap(f_up, g, (P(), bspec, sk_spec), bspec),
                    bwd=_smap(b_up, g, (P(), bspec, sk_spec, bspec),
                              (P(), bspec, sk_spec)))

            def fb_up(p, h, sk, y, _a=a, _b=b):
                def lf(p_, h_, s_):
                    logits = unet_lib.up_range(
                        p_, h_, s_, cfg, _a, _b, bn_axes=axes,
                        grad_axes=gx, precision=policy)
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1)
                    nll = -jnp.take_along_axis(
                        logp, y[..., None], axis=-1)[..., 0]
                    return jnp.sum(nll) / gv

                loss, (gp, gh, gsk) = jax.value_and_grad(
                    lf, argnums=(0, 1, 2))(p, h, sk)
                loss = lax.psum(loss, axes)
                if mode == "monolithic":
                    gp = _psum_tree(gp)
                return loss, gp, gh, gsk

            return dict(
                kind="uploss", group=g, partner=partner,
                fused=_smap(fb_up, g, (P(), bspec, sk_spec, bspec),
                            (P(), P(), bspec, sk_spec)))

        for g in range(n_grp - 1):
            nodes.append(_down_node(g, *ranges[g], core=False))
        nodes.append(_down_node(n_grp - 1, *ranges[n_grp - 1], core=True))
        for g in range(n_grp - 2, -1, -1):
            nodes.append(_up_node(g, *ranges[g], partner=g))
        loss_group = 0

    K = len(nodes)
    order = _schedule_order(K, M, sched)
    group_nodes = tuple(
        [k for k, nd in enumerate(nodes) if nd["group"] == g]
        for g in range(n_grp))

    # §13 runtime: ONE DISPATCHER THREAD PER GROUP. Each thread walks its
    # group's slice of the schedule in order, so dispatch for group g
    # never waits behind another group's host work — only on the
    # cross-group data edges (slots) the schedule actually has. Skip and
    # saved-input edges are group-resident by construction, so the only
    # cross-thread slots are the activation carry and its cotangent.
    # The sequential oracle's SYNC is a real barrier across dispatchers
    # plus a device drain of that micro-batch's loss — exactly the
    # per-micro-batch blocking GPipe-naive execution it models.
    group_ops = tuple([] for _ in range(n_grp))
    for _op in order:
        if _op[0] == "SYNC":
            for _ops in group_ops:
                _ops.append(_op)
        else:
            group_ops[nodes[_op[1]]["group"]].append(_op)
    dispatchers = _futures.ThreadPoolExecutor(
        max_workers=n_grp, thread_name_prefix="pipe-dispatch")
    # one slot per potentially in-flight boundary crossing: a link carries
    # latency, not occupancy — concurrent transfers must not queue behind
    # each other or the emulated latency multiplies instead of hiding
    link_pool = _futures.ThreadPoolExecutor(
        max_workers=min(32, max(2 * (n_grp - 1) * M, 1)),
        thread_name_prefix="pipe-link")

    def _link_put(val, dst, lat):
        # emulated inter-group link (flags.pipeline_link_latency_s): the
        # latency burns on a link thread, not a dispatcher, the way a NIC
        # would carry it — a schedule only pays it where a consumer truly
        # has nothing else to dispatch
        with trace_lib.span("pipe.link", latency_s=lat):
            time.sleep(lat)
            return jax.device_put(val, dst)

    add_tree = jax.jit(lambda u, v: jax.tree.map(jnp.add, u, v),
                       donate_argnums=(0,))
    flag_of = jax.jit(
        lambda g_: precision_lib.all_finite(g_).astype(jnp.float32))
    flag_of_loss = jax.jit(
        lambda g_, l_: (precision_lib.all_finite(g_)
                        & jnp.isfinite(l_)).astype(jnp.float32))
    if guard:
        def upd(p, s, g_, *fl):
            f = fl[0]
            for other in fl[1:]:
                f = f * other
            new_p, new_s = optimizer.update(g_, s, p)
            ok = f > 0.5
            new_p = guard_lib.tree_select(ok, new_p, p)
            new_s = guard_lib.tree_select(ok, new_s, s)
            return new_p, new_s, f
    else:
        def upd(p, s, g_):
            return optimizer.update(g_, s, p)
    upd_j = jax.jit(jax.named_scope("optimizer")(upd),
                    donate_argnums=(0, 1) if donate else ())

    def step(params, opt_states, x, y, seed):
        with trace_lib.span("pipe.place", micro_batches=M):
            pgs = [reshard_lib.to_group(pg, rep[g])
                   for g, pg in enumerate(pipeline_group_params(
                       cfg, plan, params))]
            opts = [reshard_lib.to_group(s, rep[g])
                    for g, s in enumerate(opt_states)]
            xs = [jax.device_put(x[m * mb:(m + 1) * mb], bat[0])
                  for m in range(M)]
            ys = [jax.device_put(y[m * mb:(m + 1) * mb], bat[loss_group])
                  for m in range(M)]

        carry, gcar = _Slots(), _Slots()
        for m in range(M):
            carry.set((0, m), xs[m])
        # group-resident state: every key is written and read by one
        # dispatcher thread (skips never cross a group; a node's saved
        # input backs its own recompute; acc[k] belongs to k's group)
        saved, stash, gskc = {}, {}, {}
        acc = [None] * K
        losses = [None] * M
        barrier = threading.Barrier(n_grp)

        def route(val, src_g, dst_k, slot, m):
            dst_g = nodes[dst_k]["group"]
            if dst_g == src_g:
                slot.set((dst_k, m), val)
                return
            lat = flags.get("pipeline_link_latency_s")
            slot.set((dst_k, m),
                     link_pool.submit(_link_put, val, bat[dst_g], lat)
                     if lat else reshard_lib.cross_group(val, bat[dst_g]))

        def bump(k, gp):
            acc[k] = gp if acc[k] is None else add_tree(acc[k], gp)

        track = sched == "sequential"  # 1f1b has no SYNC: don't pin refs

        def run_group(g):
            # §14: each op is a span on THIS dispatcher thread's track
            # (pipe-dispatch_g), and the cross-group handoff wait is its
            # own span — so in the exported trace the 1F1B warmup /
            # steady-state / drain structure and the bubble are visible
            # as the pipe.wait spans and the gaps between ops.
            pend = []  # this group's dispatches since the last SYNC
            for op, k, m in group_ops[g]:
                if op == "SYNC":
                    # GPipe-naive blocking: nothing from micro-batch m+1
                    # is admitted ANYWHERE until micro-batch m has fully
                    # drained — every group blocks on its own dispatches,
                    # then all dispatchers cross the barrier together
                    with trace_lib.span("pipe.sync", group=g, micro=m):
                        barrier.wait()
                        jax.block_until_ready(pend)
                        pend = []
                        barrier.wait()
                    continue
                nd = nodes[k]
                if op == "F":
                    with trace_lib.span("pipe.wait", group=g, node=k,
                                        micro=m, op="F"):
                        h = carry.take((k, m))
                    with trace_lib.span("pipe.F", group=g, node=k,
                                        micro=m):
                        if nd["kind"] == "down":
                            out, sk = nd["fwd"](pgs[g], h)
                            stash[(k, m)] = sk
                            saved[(k, m)] = (h,)
                        elif nd["kind"] == "up":
                            sk = stash[(nd["partner"], m)]
                            out = nd["fwd"](pgs[g], h, sk)
                            saved[(k, m)] = (h, sk)
                        else:  # seg / core
                            out = nd["fwd"](pgs[g], h)
                            saved[(k, m)] = (h,)
                        if track:
                            pend.append(out)
                        route(out, g, k + 1, carry, m)
                elif op == "FB":
                    with trace_lib.span("pipe.wait", group=g, node=k,
                                        micro=m, op="FB"):
                        h = carry.take((k, m))
                    with trace_lib.span("pipe.FB", group=g, node=k,
                                        micro=m):
                        if nd["kind"] == "uploss":
                            sk = stash[(nd["partner"], m)]
                            loss, gp, gh, gsk = nd["fused"](pgs[g], h, sk,
                                                            ys[m])
                            gskc[(nd["partner"], m)] = gsk
                        else:  # cosmoflow fused loss
                            loss, gp, gh = nd["fused"](pgs[g], h, ys[m],
                                                       seed, m * mb)
                        losses[m] = loss
                        bump(k, gp)
                        if track:
                            pend.append(gh)
                        route(gh, g, k - 1, gcar, m)
                else:  # B
                    with trace_lib.span("pipe.wait", group=g, node=k,
                                        micro=m, op="B"):
                        gout = gcar.take((k, m))
                    with trace_lib.span("pipe.B", group=g, node=k,
                                        micro=m):
                        if nd["kind"] == "down":
                            gsk = gskc.pop((k, m))
                            (h,) = saved.pop((k, m))
                            gp, gh = nd["bwd"](pgs[g], h, gout, gsk)
                            stash.pop((k, m), None)
                        elif nd["kind"] == "up":
                            h, sk = saved.pop((k, m))
                            gp, gh, gsk = nd["bwd"](pgs[g], h, sk, gout)
                            gskc[(nd["partner"], m)] = gsk
                        else:
                            (h,) = saved.pop((k, m))
                            gp, gh = nd["bwd"](pgs[g], h, gout)
                        if track:
                            pend.append(gh)
                        bump(k, gp)
                        if k > 0:
                            route(gh, g, k - 1, gcar, m)

        futs = [dispatchers.submit(run_group, g) for g in range(n_grp)]
        done, _ = _futures.wait(futs,
                                return_when=_futures.FIRST_EXCEPTION)
        errs = [f.exception() for f in done if f.exception() is not None]
        if errs:
            # wake every peer (blocked takes get the exception, blocked
            # barrier waits break) before re-raising the original
            barrier.abort()
            carry.fail(errs[0])
            gcar.fail(errs[0])
            _futures.wait(futs)
            raise errs[0]

        total = losses[0]
        for l in losses[1:]:
            total = total + l

        merged = []
        for g in range(n_grp):
            mg = {}
            for k in group_nodes[g]:
                mg.update(acc[k])
            merged.append(mg)

        applied = None
        with trace_lib.span("pipe.update"):
            if guard:
                fin = [flag_of_loss(merged[g], total) if g == loss_group
                       else flag_of(merged[g]) for g in range(n_grp)]
            new_pg, new_opt = [], []
            for g in range(n_grp):
                if guard:
                    fl = [fin[g]] + [
                        jax.device_put(fin[j], rep[g])
                        for j in range(n_grp) if j != g]
                    p2, s2, f = upd_j(pgs[g], opts[g], merged[g], *fl)
                    if g == 0:
                        applied = f
                else:
                    p2, s2 = upd_j(pgs[g], opts[g], merged[g])
                new_pg.append(p2)
                new_opt.append(s2)
        out_params = {}
        for pg in new_pg:
            out_params.update(pg)
        if guard:
            return out_params, tuple(new_opt), total, applied
        return out_params, tuple(new_opt), total

    return step
