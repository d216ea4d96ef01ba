"""``compile(RunConfig) -> Session``: the one assembly path (DESIGN.md §10).

The Session owns everything the drivers used to hand-assemble — the mesh,
the (possibly memory-budget-argmin'd) ``ParallelPlan``, the precision
policy, the sharded optimizer state, and the jitted step/eval closures —
behind one lifecycle:

    session = repro.api.compile(config)   # validate -> plan -> mesh -> jit
    print(session.describe())             # plan + modeled peak + model time
    loader = session.make_loader()        # plan-sharded data pipeline
    loss = session.step(batch)            # params/opt/seed threaded inside
    session.save(path); Session.restore(path)  # config embedded in ckpt

It *lowers to* ``repro.train.train_step`` — the internal layer the
existing parity/jaxpr tests pin — so a Session-driven step is the same
compiled program as the raw ``make_convnet_train_step`` path.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.config import RunConfig, RunConfigError
from repro.configs.base import ConvNetConfig
from repro.core import faults
from repro.core import flags
from repro.core import memory as memory_lib
from repro.core import plan as plan_lib
from repro.core import precision as precision_lib
from repro.core import reshard as reshard_lib
from repro.core.perf_model import V100
from repro.core.spatial_conv import SpatialPartitioning
from repro.launch import mesh as mesh_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.models import cosmoflow as cosmoflow_lib
from repro.models import unet3d as unet_lib
from repro.optim.adam import Adam, constant, linear_decay, warmup_cosine
from repro.train import checkpoint
from repro.train import train_step as train_step_lib

_META_FILE = "run_config.json"


@dataclasses.dataclass(frozen=True)
class Report:
    """``Session.describe()``: the chosen plan, the §9 modeled peak, and
    the §8 perf-model step time, as one record."""

    plan_name: str
    stages: Tuple[Tuple[int, int, Tuple[Optional[str], ...],
                        Tuple[str, ...], bool], ...]
    mesh_shape: Dict[str, int]
    precision: str
    grad_comm: str
    global_batch: int
    param_count: int
    modeled_peak: "memory_lib.MemoryBreakdown"
    memory_budget_bytes: Optional[float]
    predicted_step_s: float
    # §11 guard telemetry: skipped steps, fp16 loss scale, I/O retries,
    # auto-resumes — empty dict for a pre-guard report
    telemetry: Dict[str, float] = dataclasses.field(default_factory=dict)
    # §13 pipeline axis: stage->device-group map, device-id span per
    # group, and the modeled 1F1B bubble — all None without pipelining
    stage_groups: Optional[Tuple[int, ...]] = None
    group_devices: Optional[Tuple[Tuple[int, int], ...]] = None
    micro_batches: Optional[int] = None
    pipeline_schedule: Optional[str] = None
    bubble_fraction: Optional[float] = None

    def __str__(self) -> str:
        budget = ("none" if self.memory_budget_bytes is None
                  else f"{self.memory_budget_bytes / 2 ** 30:.2f}GiB")
        stages = "; ".join(
            f"[{a},{b}) spatial={[x for x in sp if x]} batch={list(ba)}"
            + (" remat" if rm else "")
            for a, b, sp, ba, rm in self.stages)
        pipe = ""
        if self.stage_groups is not None:
            assign = "; ".join(
                f"stage{i}[{a},{b})->group{g} devices[{lo},{hi})"
                for i, ((a, b, _, _, _), g) in enumerate(
                    zip(self.stages, self.stage_groups))
                for lo, hi in [self.group_devices[g]])
            pipe = (
                f"\n  pipeline: {len(self.group_devices)} groups  "
                f"micro_batches={self.micro_batches}  "
                f"schedule={self.pipeline_schedule}  "
                f"bubble={self.bubble_fraction:.1%}\n"
                f"  groups: {assign}")
        return (
            f"Session[{self.plan_name}]\n"
            f"  mesh {self.mesh_shape}  precision={self.precision}  "
            f"grad_comm={self.grad_comm}  global_batch={self.global_batch}\n"
            f"  stages: {stages}"
            f"{pipe}\n"
            f"  params {self.param_count / 1e6:.2f}M  "
            f"modeled peak/device {self.modeled_peak.describe()}\n"
            f"  budget {budget}  predicted step "
            f"{self.predicted_step_s * 1e3:.2f}ms (perf model, V100)"
            + (("\n  guard: " + "  ".join(
                f"{k}={v:g}" for k, v in sorted(self.telemetry.items())))
               if self.telemetry else ""))


def _build_optimizer(config: RunConfig) -> Adam:
    if config.lr_schedule == "constant":
        sched = constant(config.lr)
    elif config.lr_schedule == "linear_decay":
        sched = linear_decay(config.lr, config.total_steps)
    else:
        sched = warmup_cosine(config.lr, config.warmup_steps,
                              config.total_steps)
    return Adam(lr=sched, grad_clip=config.grad_clip)


def _spatial_options(cfg: ConvNetConfig, config: RunConfig) -> Tuple[int, ...]:
    """Spatial degrees the budgeted planner may raise to: powers of two
    from the configured degree while the device count and the layer-0
    local width admit them (DESIGN.md §9's capacity escape hatch)."""
    opts, s = [], max(config.spatial, 1)
    dev = jax.device_count()
    while (config.data * s <= dev and cfg.input_width % s == 0
           and cfg.input_width // s >= 4):
        opts.append(s)
        s *= 2
    return tuple(opts) or (config.spatial,)


def _pipeline_degree_options(pipeline: int) -> Tuple[int, ...]:
    """Pipeline group counts ``plan="auto"`` may pick from: powers of two
    up to the configured ceiling, plus the ceiling itself."""
    opts = {pipeline} | {2 ** k for k in range(1, pipeline.bit_length())
                         if 2 ** k <= pipeline}
    return tuple(sorted(p for p in opts if p > 1))


def _with_schedule(plan: "plan_lib.ParallelPlan",
                   schedule: str) -> "plan_lib.ParallelPlan":
    """Re-pin a pipelined plan's schedule (the planner prices 1F1B; a
    config asking for the sequential oracle keeps the same groups)."""
    spec = plan.pipeline
    if spec is None or spec.schedule == schedule:
        return plan
    return dataclasses.replace(
        plan, pipeline=dataclasses.replace(spec, schedule=schedule),
        name=plan.name.replace(f".{spec.schedule}", f".{schedule}"))


def _resolve_plan(config: RunConfig, cfg: ConvNetConfig,
                  grad_comm: str) -> Tuple["plan_lib.ParallelPlan", str]:
    """(plan, precision name) for a validated config."""
    explicit = None if config.precision == "auto" else config.precision
    if isinstance(config.plan, plan_lib.ParallelPlan):
        return config.plan, explicit or config.plan.precision
    if config.plan == "fixed" and config.pipeline > 1:
        # fixed + pipeline: exactly the configured group count and
        # micro-batch count; the perf model argmins only the boundary.
        cands = plan_lib.candidate_pipeline_plans(
            cfg, V100, pipeline_degrees=(config.pipeline,),
            micro_batch_options=(config.micro_batches,),
            num_devices=config.data, global_batch=config.global_batch,
            grad_comm=grad_comm, schedule=config.pipeline_schedule)
        if not cands:
            raise RunConfigError(
                "pipeline",
                f"no admissible {config.pipeline}-group split of "
                f"{cfg.name} at data={config.data}, micro_batches="
                f"{config.micro_batches}",
                "lower pipeline/micro_batches, or make data a multiple "
                "of pipeline")
        plan = min(cands, key=lambda p: p.cost)
        return plan, explicit or plan.precision
    if config.plan == "auto" or config.memory_budget_gib is not None:
        kw: Dict[str, Any] = dict(
            spatial_degree=config.spatial, data_degree=config.data,
            global_batch=config.global_batch, grad_comm=grad_comm)
        if config.pipeline > 1:
            # auto + pipeline ceiling: the joint argmin may pick any
            # group count up to the ceiling — or no pipelining at all.
            kw.update(
                pipeline_options=_pipeline_degree_options(config.pipeline),
                micro_batch_options=(config.micro_batches,))
        if config.memory_budget_gib is not None:
            budget = config.memory_budget_gib * 2 ** 30
            precisions = (explicit,) if explicit else ("fp32", "bf16")
            options = _spatial_options(cfg, config)
            kw.update(memory_budget_bytes=budget, precisions=precisions,
                      spatial_options=options)
            try:
                plan = plan_lib.plan_convnet(cfg, V100, **kw)
            except ValueError as e:
                # the planner attaches the min modeled peak over every
                # candidate it priced — the floor the error reports
                mem = getattr(e, "best_infeasible_mem", None)
                if mem is None:
                    raise RunConfigError(
                        "spatial", str(e),
                        "no admissible plan at these degrees; lower "
                        "spatial or raise the device count") from e
                raise RunConfigError(
                    "memory_budget_gib",
                    f"{config.memory_budget_gib:.3f} GiB is below every "
                    f"feasible plan",
                    f"raise to at least {mem.total / 2 ** 30:.3f} GiB "
                    f"(the {e.best_infeasible_plan.name} floor over "
                    f"spatial options {list(options)}), add devices, or "
                    f"allow lower precision") from e
            plan = _with_schedule(plan, config.pipeline_schedule)
            return plan, explicit or plan.precision
        if explicit:
            kw["precisions"] = (explicit,)
        plan = _with_schedule(plan_lib.plan_convnet(cfg, V100, **kw),
                              config.pipeline_schedule)
        return plan, explicit or plan.precision
    # "fixed": the legacy fixed-degree layout (over-decomposition gathers
    # + replicated FC head), exactly what the kwarg path defaulted to
    plan = plan_lib.legacy_convnet_plan(
        cfg, SpatialPartitioning(("model", None, None)),
        (config.spatial, 1, 1), data_degrees=(config.data,))
    return plan, explicit or "fp32"


def _commit_state(params, opt_state, mesh, plan, grad_comm):
    """Place fresh state where the step's shard_map leaves its outputs:
    params replicated, a ZeRO-1 (``reduce_scatter``) state's flat
    buckets sharded over the entry stage's batch axes, scalars
    replicated. Uncommitted arrays would give the first step a program
    that no later step reuses, so the second step would compile again."""
    rep = NamedSharding(mesh, P())
    flat = NamedSharding(mesh, P(tuple(plan.stages[0].batch_axes)))

    def place(leaf):
        sharded = grad_comm == "reduce_scatter" and leaf.ndim
        return jax.device_put(leaf, flat if sharded else rep)

    return jax.device_put(params, rep), jax.tree.map(place, opt_state)


def compile(config: RunConfig):  # noqa: A001 - the API verb
    """Validate ``config``, resolve plan/precision/grad-comm, build the
    mesh and optimizer state, and return a live ``Session`` — or, for
    ``mode="infer"``, a forward-only ``InferenceSession`` (DESIGN.md
    §15: no optimizer state, the same plan-sharded forward)."""
    if config.mode == "infer":
        # deferred: repro.serve.session imports this module
        from repro.serve.session import compile_infer

        return compile_infer(config)
    return _compile(config, abstract_state=False)


def _compile(config: RunConfig, *, abstract_state: bool) -> "Session":
    """``abstract_state=True`` builds params/opt-state as ``eval_shape``
    templates instead of materialized arrays — ``Session.restore`` only
    needs their tree structure before overwriting them from disk."""
    config.validate()
    cfg = config.resolve_model()
    grad_comm = (config.grad_comm if config.grad_comm != "auto"
                 else flags.get("grad_comm"))
    plan, precision = _resolve_plan(config, cfg, grad_comm)
    pipelined = plan.n_groups > 1
    meshes = mesh_lib.make_pipeline_meshes(plan) if pipelined else None
    mesh = meshes[0] if pipelined else mesh_lib.make_plan_mesh(plan)
    optimizer = _build_optimizer(config)
    init_fn = (cosmoflow_lib.init_params if cfg.arch == "cosmoflow"
               else unet_lib.init_params)

    def build_state():
        params = init_fn(jax.random.PRNGKey(config.seed), cfg)
        if pipelined:
            opt_state = train_step_lib.make_pipeline_opt_state(
                cfg, optimizer, params, plan=plan,
                meshes=None if abstract_state else meshes,
                precision=precision)
        else:
            opt_state = train_step_lib.make_convnet_opt_state(
                cfg, optimizer, params, mesh=mesh, grad_comm=grad_comm,
                plan=plan, precision=precision)
        return params, opt_state

    params, opt_state = (jax.eval_shape(build_state) if abstract_state
                         else build_state())
    if not (abstract_state or pipelined):
        params, opt_state = _commit_state(params, opt_state, mesh, plan,
                                          grad_comm)
    if pipelined:
        step_fn = train_step_lib.make_pipeline_train_step(
            cfg, meshes, optimizer, plan=plan,
            global_batch=config.global_batch, grad_comm=grad_comm,
            precision=precision, guard=config.resolved_guard)
    else:
        step_fn = train_step_lib.make_convnet_train_step(
            cfg, mesh, optimizer, global_batch=config.global_batch,
            use_pallas=config.use_pallas, overlap=config.overlap_halo,
            grad_comm=grad_comm, plan=plan, precision=precision,
            guard=config.resolved_guard)
    return Session(config, cfg, mesh, plan, precision, grad_comm,
                   optimizer, params, opt_state, step_fn, meshes=meshes)


class Session:
    """A compiled hybrid-parallel training run. Build with
    ``repro.api.compile`` (or ``Session.restore``), not directly."""

    def __init__(self, config, cfg, mesh, plan, precision, grad_comm,
                 optimizer, params, opt_state, step_fn, meshes=None):
        self.config: RunConfig = config
        self.cfg: ConvNetConfig = cfg
        self.mesh = mesh
        # §13: one mesh per pipeline device group (None when unpipelined);
        # self.mesh stays group 0's mesh, which eval/restore reuse
        self.meshes = meshes
        self.plan: plan_lib.ParallelPlan = plan
        self.precision: str = precision_lib.get(precision).name
        self.grad_comm: str = grad_comm
        self.optimizer = optimizer
        self.params = params
        self.opt_state = opt_state
        self._step_fn = step_fn
        self._t = 0
        self._eval_fns: Dict[Any, Any] = {}
        self._tmpdirs = []
        self._loaders = []
        # §11 telemetry: guarded-step skip counter kept as a lazy jax
        # accumulator (no per-step host sync), resumes set by the
        # supervisor / restore path
        self._guarded_steps = 0
        self._applied_acc = None  # set at the first guarded step
        self.resumes = 0
        # §14 observability: every Session owns a Tracer + registry; the
        # tracer only becomes the process-active one (and thus receives
        # spans from the dispatcher/loader/checkpoint seams) when
        # config.trace asks for it — otherwise every instrumentation
        # site stays on the near-free no-op path.
        self._closed = False
        self._close_lock = threading.Lock()
        self.tracer = trace_lib.Tracer()
        self._metrics = metrics_lib.MetricsRegistry()
        self._trace_path = (config.trace if isinstance(config.trace, str)
                            else None)
        self._exported_traces: set = set()
        self._metrics_sink = None
        if config.metrics_jsonl:
            d = os.path.dirname(config.metrics_jsonl)
            if d:
                os.makedirs(d, exist_ok=True)
            self._metrics_sink = metrics_lib.MetricsJsonlSink(
                config.metrics_jsonl)
        if config.trace:
            trace_lib.enable(self.tracer)

    # ----------------------------------------------------------- train ----
    @property
    def step_count(self) -> int:
        return self._t

    def step(self, batch, y=None):
        """Run one training step on a global batch (an ``(x, y)`` pair,
        or ``step(x, y)``) and return the loss. Params, optimizer state,
        and the per-step dropout seed are threaded internally; the
        checkpoint policy (``save_every``) fires here.

        §11 fault sites fire here too: ``comm.stall`` (host-side sleep
        the supervisor's watchdog must catch), ``device.loss`` (raises
        ``DeviceLost``), and ``grads.nonfinite`` (poisons the batch so
        the in-graph guard must skip the update)."""
        x, y = batch if y is None else (batch, y)
        sink = self._metrics_sink
        t0 = time.perf_counter() if sink is not None else 0.0
        with trace_lib.span("train.step", step=self._t):
            faults.fire("comm.stall", step=self._t)
            faults.fire("device.loss", step=self._t)
            if faults.fire("grads.nonfinite", step=self._t):
                x = x * jnp.nan  # loss and every gradient go non-finite
            seed = jnp.asarray(self._t, jnp.int32)
            if self.config.resolved_guard:
                self.params, self.opt_state, loss, applied = self._step_fn(
                    self.params, self.opt_state, x, y, seed)
                self._guarded_steps += 1
                if self._applied_acc is None:
                    # laid out like `applied`, so the add compiles once
                    self._applied_acc = jnp.zeros_like(applied)
                self._applied_acc = self._applied_acc + applied
            else:
                self.params, self.opt_state, loss = self._step_fn(
                    self.params, self.opt_state, x, y, seed)
            self._t += 1
        if sink is not None:
            # host-visible counters only: converting loss (or the lazy
            # skip accumulator) would force a device sync per step
            row = {"step": self._t - 1,
                   "wall_s": time.perf_counter() - t0,
                   "guarded_steps": self._guarded_steps}
            stall = sum(getattr(ld, "stall_s", 0.0) for ld in self._loaders)
            if self._loaders:
                row["io_stall_s"] = stall
            sink.write(row)
        if (self.config.checkpoint_dir and self.config.save_every
                and self._t % self.config.save_every == 0):
            if self.config.keep_last is not None:
                self.save(checkpoint.step_dir(self.config.checkpoint_dir,
                                              self._t))
                checkpoint.gc_steps(self.config.checkpoint_dir,
                                    self.config.keep_last)
            else:
                self.save()
        return loss

    def evaluate(self, x, y):
        """(loss, predictions) on an eval batch. CosmoFlow returns the
        regression MSE and per-sample predictions (sharded over the FC
        stage's batch axes); the U-Net returns the voxel cross-entropy
        and the per-voxel logits in the plan's level-0 layout (the loss
        ops mirror ``segmentation_loss`` exactly, so it stays bitwise
        with the old fwd-probe path)."""
        gb = int(x.shape[0])
        key = ("eval", gb)
        fn = self._eval_fns.get(key)
        params = self.params
        if self.plan.n_groups > 1:
            # §13: gather the per-group param subsets onto group 0's mesh
            # — a pipelined plan's stages all share one trivial layout, so
            # the whole model evaluates as plain data parallelism there
            params = reshard_lib.to_group(
                params, jax.sharding.NamedSharding(self.mesh, P()))
        if fn is None:
            fn = train_step_lib.make_convnet_eval_step(
                self.cfg, self.mesh, global_batch=gb, plan=self.plan,
                use_pallas=self.config.use_pallas,
                overlap=self.config.overlap_halo,
                precision=self.precision)
            self._eval_fns[key] = fn
        return fn(params, x, y)

    # --------------------------------------------------- introspection ----
    def _skipped(self) -> float:
        """Guarded steps whose update was vetoed (syncs the lazy
        accumulator)."""
        if self._applied_acc is None:
            return 0.0
        return self._guarded_steps - float(self._applied_acc)

    def telemetry(self) -> Dict[str, float]:
        """§11 guard/recovery counters: ``skipped_steps`` (guarded steps
        whose update was vetoed), ``loss_scale`` (the live fp16 scale, 1
        otherwise), ``loader_retries`` (transient store-read failures
        absorbed by backoff, summed over this Session's loaders), and
        ``resumes`` (checkpoint auto-resumes, set by the supervisor).
        Reading ``skipped_steps`` syncs the lazy accumulator.
        ``pool_index_share`` is the share of the model's max-pools that
        lower through the saved window index under the plan
        (``core/spatial_conv.py``), 0 where all fall back to
        ``reduce_window``.

        §12 input-pipeline counters ride along, summed over this
        Session's loaders: ``io_pfs_bytes`` (store bytes actually read),
        ``io_cache_hit_ratio`` (fraction of loader bytes served from the
        distributed cache), ``io_in_place_share`` (fraction of the store
        bytes read straight into a batch buffer), and — when any loader
        prefetches —
        ``io_stall_s`` (residual time steps still blocked on a queued
        batch) and ``io_queue_occupancy`` (mean prefetch-queue depth at
        serve time; ~depth when the pipeline keeps up).

        §14: every value is routed through the Session's
        ``MetricsRegistry`` gauges and the returned dict is read back
        out of the registry — same keys, same values, one metrics
        surface (``session._metrics``) for every other consumer."""
        skipped = self._skipped()
        scale = (float(self.opt_state.loss_scale)
                 if isinstance(self.opt_state, precision_lib.MPState)
                 else 1.0)
        retries = sum(ld.store.retries for ld in self._loaders)
        out = {"steps": float(self._t),
               "skipped_steps": round(skipped),
               "loss_scale": scale,
               "loader_retries": float(retries),
               "resumes": float(self.resumes),
               "pool_index_share": memory_lib.pool_index_share(self.cfg,
                                                               self.plan)}
        if self._loaders:
            out["io_pfs_bytes"] = float(
                sum(ld.stats.pfs_bytes for ld in self._loaders))
            served = sum(
                ld.stats.pfs_bytes + ld.stats.cache_bytes_local
                + ld.stats.cache_bytes_redistributed for ld in self._loaders)
            out["io_cache_hit_ratio"] = (
                1.0 - out["io_pfs_bytes"] / served if served else 0.0)
            in_place = sum(ld.stats.bytes_in_place for ld in self._loaders)
            out["io_in_place_share"] = (
                in_place / out["io_pfs_bytes"] if out["io_pfs_bytes"]
                else 0.0)
            async_loaders = [ld for ld in self._loaders
                             if hasattr(ld, "queue_occupancy")]
            if async_loaders:
                out["io_stall_s"] = sum(ld.stall_s for ld in async_loaders)
                out["io_queue_occupancy"] = (
                    sum(ld.queue_occupancy() for ld in async_loaders)
                    / len(async_loaders))
        return self._metrics.absorb(out)

    def describe(self) -> Report:
        """One report: the chosen plan, the modeled per-device peak
        (``core/memory.py``), and the predicted step time
        (``core/perf_model.py``)."""
        priced = (self.plan if self.plan.precision == self.precision
                  else dataclasses.replace(self.plan,
                                           precision=self.precision))
        t = plan_lib.price_plan(self.cfg, V100, priced,
                                global_batch=self.config.global_batch,
                                grad_comm=self.grad_comm)
        peak = memory_lib.plan_peak_bytes(
            self.cfg, self.plan, global_batch=self.config.global_batch,
            grad_comm=self.grad_comm, precision=self.precision)
        budget = (None if self.config.memory_budget_gib is None
                  else self.config.memory_budget_gib * 2 ** 30)
        pipe: Dict[str, Any] = {}
        if self.plan.pipeline is not None and self.plan.n_groups > 1:
            spec = self.plan.pipeline
            d = self.plan.data_degree
            pipe = dict(
                stage_groups=tuple(spec.stage_groups),
                group_devices=tuple((g * d, (g + 1) * d)
                                    for g in range(self.plan.n_groups)),
                micro_batches=spec.micro_batches,
                pipeline_schedule=spec.schedule,
                bubble_fraction=spec.bubble_fraction)
        return Report(
            plan_name=self.plan.name,
            stages=tuple((s.start, s.stop, tuple(s.spatial_axes),
                          tuple(s.batch_axes), s.remat)
                         for s in self.plan.stages),
            mesh_shape=dict(self.mesh.shape),
            precision=self.precision,
            grad_comm=self.grad_comm,
            global_batch=self.config.global_batch,
            param_count=self.cfg.param_count(),
            modeled_peak=peak,
            memory_budget_bytes=budget,
            predicted_step_s=t,
            telemetry=self.telemetry(),
            **pipe)

    def profile(self, batch=None, reps: int = 3) -> Dict[str, float]:
        """Measured phase attribution (DESIGN.md §4): seconds for the
        ``fwd``/``bwd``/``grad_comm``/``step`` probes plus the derived
        per-phase splits (``backward``, ``comm``, ``optimizer``).
        ``batch=None`` profiles a synthetic batch.

        A pipelined session (§13) has no shard_map phase probes — its
        phases interleave across device groups by construction — so it
        reports the full step under the plan's schedule (``step``) and
        under the blocking sequential oracle (``step_sequential``), plus
        the measured ``pipeline_speedup`` ratio."""
        x, y = batch if batch is not None else self._synthetic_batch()
        if self.plan.n_groups > 1:
            return self._profile_pipeline(x, y, reps)
        probes = train_step_lib.make_convnet_phase_probes(
            self.cfg, self.mesh, self.optimizer,
            global_batch=self.config.global_batch,
            use_pallas=self.config.use_pallas,
            overlap=self.config.overlap_halo, grad_comm=self.grad_comm,
            plan=self.plan, precision=self.precision)
        seed = jnp.asarray(0, jnp.int32)
        out: Dict[str, float] = {}
        for stage, fn in probes.items():
            jax.block_until_ready(fn(self.params, self.opt_state, x, y,
                                     seed))  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                # §14: each rep is a span, so a tracing session's drift
                # table reads its measured phases from the span
                # aggregates rather than from this function's return
                with trace_lib.span(f"probe.{stage}"):
                    r = fn(self.params, self.opt_state, x, y, seed)
                    jax.block_until_ready(r)
            out[stage] = (time.perf_counter() - t0) / reps
        out["backward"] = max(out["bwd"] - out["fwd"], 0.0)
        out["comm"] = max(out["grad_comm"] - out["bwd"], 0.0)
        out["optimizer"] = max(out["step"] - out["grad_comm"], 0.0)
        for k, v in self.telemetry().items():
            out[f"telemetry.{k}"] = v
        return out

    def _profile_pipeline(self, x, y, reps: int) -> Dict[str, float]:
        seed = jnp.asarray(0, jnp.int32)
        out: Dict[str, float] = {}
        for label, sched in (("step", None), ("step_sequential",
                                              "sequential")):
            fn = train_step_lib.make_pipeline_train_step(
                self.cfg, self.meshes, self.optimizer, plan=self.plan,
                global_batch=self.config.global_batch,
                grad_comm=self.grad_comm, precision=self.precision,
                schedule=sched, donate=False)
            jax.block_until_ready(fn(self.params, self.opt_state, x, y,
                                     seed))  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                with trace_lib.span(f"probe.{label}"):
                    r = fn(self.params, self.opt_state, x, y, seed)
                    jax.block_until_ready(r)
            out[label] = (time.perf_counter() - t0) / reps
        out["pipeline_speedup"] = (out["step_sequential"] / out["step"]
                                   if out["step"] else 0.0)
        for k, v in self.telemetry().items():
            out[f"telemetry.{k}"] = v
        return out

    def report(self, batch=None, reps: int = 2,
               flag_ratio: float = 2.0):
        """Modeled-vs-measured drift table (DESIGN.md §14): the §8 perf
        model's predicted per-phase seconds against measured span
        aggregates, per-phase ratio flagged when off by more than
        ``flag_ratio`` in either direction.

        The measured column is sourced from spans: the phase probes are
        run under this Session's tracer if their ``probe.*`` aggregates
        are not already populated (a loader batch is driven the same way
        for the ``io`` row), then the table reads
        ``tracer.span_seconds()`` — never a probe's return dict. An
        untraced session's tracer is activated only for the duration of
        this call."""
        from repro.obs import report as drift_lib

        prev = trace_lib.active()
        trace_lib.enable(self.tracer)
        try:
            have = self.tracer.span_seconds()
            pipelined = self.plan.n_groups > 1
            probes = (("step",) if pipelined
                      else ("fwd", "bwd", "grad_comm", "step"))
            if not all(f"probe.{p}" in have for p in probes):
                self.profile(batch, reps=reps)
            have = self.tracer.span_seconds()
            if "io.load" not in have:
                self._drive_io_sample()
        finally:
            if prev is not None and prev is not self.tracer:
                trace_lib.enable(prev)
            elif not self.config.trace:
                trace_lib.disable(self.tracer)
        modeled = drift_lib.modeled_phases(
            self.cfg, V100, self.plan,
            global_batch=self.config.global_batch,
            grad_comm=self.grad_comm, precision=self.precision)
        measured = drift_lib.measured_phases(self.tracer)
        return drift_lib.drift(modeled, measured, flag_ratio=flag_ratio)

    def _drive_io_sample(self, batches: int = 2) -> None:
        """Load a couple of real batches through a (possibly existing)
        loader so the drift table's ``io`` row has span data."""
        gb = self.config.global_batch
        loader = (self._loaders[-1] if self._loaders
                  else self.make_loader(num_samples=max(gb, 4)))
        order = loader.schedule_for_epoch(0)
        n = max(len(order) // gb, 1)
        for b in range(min(batches, n)):
            jax.block_until_ready(
                loader.load_batch(order[b * gb:(b + 1) * gb]))

    def _synthetic_batch(self):
        w, gb = self.cfg.input_width, self.config.global_batch
        kx, ky = jax.random.split(jax.random.PRNGKey(self.config.seed + 1))
        x = jax.random.normal(kx, (gb, w, w, w, self.cfg.in_channels))
        if self.cfg.arch == "cosmoflow":
            y = jax.random.normal(ky, (gb, self.cfg.out_dim))
        else:
            y = jax.random.randint(ky, (gb, w, w, w), 0, self.cfg.out_dim)
        return x, y

    # ------------------------------------------------------------ data ----
    def make_loader(self, root: Optional[str] = None, *,
                    num_samples: int = 16, seed: int = 0, cache: bool = True,
                    prefetch: Optional[int] = None, halo_voxels: int = 0):
        """A loader sharded for the plan's entry stage. ``root`` (or
        ``config.data_dir``) names an existing ``HyperslabStore``; with
        neither, a synthetic dataset of ``num_samples`` volumes is
        written to a Session-owned temp dir.

        ``prefetch`` (default ``config.prefetch``) selects the input
        pipeline (DESIGN.md §12): 0 returns the synchronous
        ``SpatialParallelLoader`` (the bitwise oracle); >= 1 wraps it in
        a ``PrefetchLoader`` of that queue depth, whose worker overlaps
        the next batch's store reads and host->device transfer with the
        current step's compute. The surface is identical either way.
        ``halo_voxels`` widens each shard's reads by that margin."""
        from repro.data import pipeline, prefetch as prefetch_lib
        from repro.data import store, synthetic

        root = root or self.config.data_dir
        if root is None:
            tmp = tempfile.TemporaryDirectory()
            self._tmpdirs.append(tmp)
            root = tmp.name
            if self.cfg.arch == "cosmoflow":
                cubes, targets = synthetic.make_cosmology_dataset(
                    num_samples, self.cfg.input_width,
                    channels=self.cfg.in_channels, seed=seed)
                store.write_dataset(root, cubes, targets)
            else:
                cubes, labels = synthetic.make_segmentation_dataset(
                    num_samples, self.cfg.input_width,
                    num_classes=self.cfg.out_dim,
                    channels=self.cfg.in_channels, seed=seed)
                store.write_dataset(root, cubes, labels=labels)
        entry = self.plan.stages[0]
        dspec = (tuple(entry.batch_axes) if len(entry.batch_axes) > 1
                 else entry.batch_axes[0])
        x_spec = P(dspec, *entry.spatial_axes, None)
        label_spec = (P(dspec, *entry.spatial_axes)
                      if self.cfg.arch == "unet3d" else None)
        loader = pipeline.SpatialParallelLoader(
            store.HyperslabStore(root), self.mesh, x_spec,
            global_batch=self.config.global_batch, seed=seed, cache=cache,
            label_spec=label_spec, halo_voxels=halo_voxels)
        depth = self.config.prefetch if prefetch is None else prefetch
        if depth:
            loader = prefetch_lib.PrefetchLoader(loader, depth=depth)
        self._loaders.append(loader)  # §11/§12 telemetry + close()
        return loader

    # ------------------------------------------------------ checkpoint ----
    def save(self, path: Optional[str] = None) -> str:
        """Checkpoint params + optimizer state (fp32 masters, per-leaf
        PartitionSpecs) AND the resolved run description, so
        ``Session.restore(path)`` rebuilds the whole run from the
        checkpoint alone. The whole directory — leaves, manifest with
        per-leaf CRCs, and the embedded config — is published by one
        atomic rename (§11): a crash mid-save cannot corrupt an existing
        checkpoint."""
        path = path or self.config.checkpoint_dir
        if path is None:
            raise ValueError("no path: pass save(path) or set "
                             "RunConfig.checkpoint_dir")
        meta = {"run_config": self._pinned_config().to_json()}
        checkpoint.save(path, {"params": self.params, "opt": self.opt_state},
                        step=self._t, precision=self.precision,
                        extra_files={_META_FILE: meta})
        return path

    def _pinned_config(self) -> RunConfig:
        """The config with every ``"auto"`` resolved: the concrete model,
        the chosen plan, precision, grad-comm, and the plan's actual
        degrees (a budgeted planner may have raised ``spatial``).
        ``data`` is the TOTAL data degree across groups (§13), so a
        restore recomputes the same per-group split."""
        pipe: Dict[str, Any] = {}
        if self.plan.pipeline is not None and self.plan.n_groups > 1:
            pipe = dict(micro_batches=self.plan.pipeline.micro_batches,
                        pipeline_schedule=self.plan.pipeline.schedule)
        return dataclasses.replace(
            self.config, model=self.cfg, plan=self.plan,
            precision=self.precision, grad_comm=self.grad_comm,
            data=self.plan.data_degree * self.plan.n_groups,
            spatial=self.plan.spatial_degree,
            pipeline=self.plan.n_groups, **pipe)

    @classmethod
    def restore(cls, path: str) -> "Session":
        """Rebuild a Session from a checkpoint directory alone: the
        embedded config reconstructs mesh/plan/precision/step, then
        params and (possibly ZeRO-1-sharded) optimizer state are
        re-placed under their recorded PartitionSpecs. Continued
        training is bitwise-identical to the uninterrupted run.

        ``path`` may also be a retention ROOT of ``step_<n>``
        checkpoints (``keep_last``/supervisor layout): the newest step
        that passes CRC validation is restored — a corrupt or partial
        newest checkpoint falls back to its predecessor (§11)."""
        if not os.path.exists(os.path.join(path, _META_FILE)):
            for _, p in reversed(checkpoint.list_steps(path)):
                if checkpoint.validate(p):
                    return cls.restore(p)
            raise FileNotFoundError(
                f"no checkpoint at {path}: neither {_META_FILE} nor a "
                f"valid step_<n> directory")
        with open(os.path.join(path, _META_FILE)) as f:
            meta = json.load(f)
        config = RunConfig.from_json(meta["run_config"])
        # abstract templates: only the tree STRUCTURE seeds the restore;
        # every leaf is overwritten from disk
        sess = _compile(config, abstract_state=True)
        tree = checkpoint.restore(
            path, {"params": sess.params, "opt": sess.opt_state},
            mesh=sess.mesh)
        sess.params, sess.opt_state = tree["params"], tree["opt"]
        sess._t = checkpoint.latest_step(path)
        return sess

    # ------------------------------------------------------- lifecycle ----
    def export_trace(self, path: Optional[str] = None) -> str:
        """Write the Session's span log as a Chrome/Perfetto
        ``trace.json`` and return the path actually written.

        A path this Session already exported to is overwritten (the
        longer trace supersedes it); a PRE-EXISTING file from another
        run is never clobbered — the export uniquifies to
        ``name-1.json``, ``name-2.json``, … so a supervisor's restarted
        sessions each get their own file instead of interleaving."""
        path = path or self._trace_path
        if path is None:
            raise ValueError("no path: pass export_trace(path) or set "
                             "RunConfig(trace='out/trace.json')")
        if path not in self._exported_traces and os.path.exists(path):
            base, ext = os.path.splitext(path)
            i = 1
            while os.path.exists(f"{base}-{i}{ext}"):
                i += 1
            path = f"{base}-{i}{ext}"
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.tracer.export_chrome(path)
        self._exported_traces.add(path)
        return path

    def close(self) -> None:
        """Drain every loader (prefetch workers stop before their store
        goes away — §12), drop Session-owned temp datasets, and flush
        the §14 trace/metrics sinks: a configured trace path is
        exported, the JSONL sink is closed, and the tracer is
        deregistered so a successor session's spans never interleave
        into this run's file. Idempotent AND thread-safe — a second
        ``close`` (``with`` + supervisor both closing, or a serve-side
        thread racing the main one) is a no-op, and exactly one caller
        performs the teardown."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for ld in self._loaders:
            ld.close()
        self._loaders = []
        for tmp in self._tmpdirs:
            tmp.cleanup()
        self._tmpdirs = []
        if self._metrics_sink is not None:
            self._metrics_sink.close()
        if self._trace_path and len(self.tracer):
            self.export_trace(self._trace_path)
        trace_lib.disable(self.tracer)

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
