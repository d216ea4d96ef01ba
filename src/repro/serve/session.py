"""Forward-only inference sessions (DESIGN.md §15).

``compile_infer(RunConfig(mode="infer")) -> InferenceSession`` is the
serving counterpart of ``repro.api.compile``: the same validate ->
plan -> mesh assembly path, but the program it builds is the
plan-sharded FORWARD only — no optimizer state, no gradient reduction.
The request buffer is not donated: no output of either model has the
volume's shape, so XLA could never reuse it, and donation would only
delete the caller's array. The forward reuses the §3 overlapped-halo
conv and §5 in-graph resharding, which is the paper's capacity argument
applied to serving: a volume too large for one device's memory is
served across the spatial group, and ``core.memory.infer_peak_bytes``
prices the per-device peak falling with the spatial degree.

Checkpoints written by training ``Session.save`` restore directly:
``InferenceSession.restore(path)`` reads the embedded run config,
strips the training-only knobs, partially restores ONLY the ``params``
subtree (the optimizer state on disk is never touched), and casts the
fp32 masters to the serving dtype once at load — after which the
forward's per-use cast is the identity, so a bf16 serving forward is
bitwise-equal to the training-time eval forward.

Batched serving rides on top: ``InferenceSession.serve()`` returns a
``ServingHarness`` (``repro.serve.harness``) whose worker threads feed
coalesced micro-batches into the session's jitted forward.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.config import RunConfig, RunConfigError
from repro.api import session as session_lib
from repro.configs.base import ConvNetConfig
from repro.core import flags
from repro.core import memory as memory_lib
from repro.core import plan as plan_lib
from repro.core import precision as precision_lib
from repro.launch import mesh as mesh_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.models import cosmoflow as cosmoflow_lib
from repro.models import unet3d as unet_lib
from repro.train import checkpoint
from repro.train import train_step as train_step_lib

# training-only knobs stripped when an embedded training config is
# repurposed for serving (RunConfig.validate would reject them under
# mode="infer")
_TRAIN_ONLY = dict(mode="infer", guard=None, grad_comm="auto",
                   pipeline=1, micro_batches=4, pipeline_schedule="1f1b",
                   save_every=None, keep_last=None, metrics_jsonl=None,
                   prefetch=0)


@dataclasses.dataclass(frozen=True)
class InferReport:
    """``InferenceSession.describe()``: the serving plan and the §15
    modeled forward-only peak."""

    plan_name: str
    mesh_shape: Dict[str, int]
    precision: str
    param_count: int
    modeled_peak: "memory_lib.MemoryBreakdown"

    def __str__(self) -> str:
        return (
            f"InferenceSession[{self.plan_name}]\n"
            f"  mesh {self.mesh_shape}  precision={self.precision}\n"
            f"  params {self.param_count / 1e6:.2f}M  "
            f"modeled forward peak/device {self.modeled_peak.describe()}")


def compile_infer(config: RunConfig) -> "InferenceSession":
    """Validate ``config`` (``mode`` must be ``"infer"``), resolve
    plan/precision, build the mesh, and return a live
    ``InferenceSession`` with freshly initialized params."""
    return _compile_infer(config, abstract_params=False)


def _compile_infer(config: RunConfig, *,
                   abstract_params: bool) -> "InferenceSession":
    if config.mode != "infer":
        raise RunConfigError(
            "mode", f"compile_infer got mode={config.mode!r}",
            "set RunConfig(mode='infer') (repro.api.compile dispatches "
            "on it)")
    config.validate()
    cfg = config.resolve_model()
    # grad_comm only parameterizes the planner's comm pricing here — the
    # compiled program reduces nothing
    plan, precision = session_lib._resolve_plan(config, cfg,
                                                flags.get("grad_comm"))
    if plan.n_groups > 1:
        raise RunConfigError(
            "plan",
            f"plan {plan.name!r} is pipelined ({plan.n_groups} device "
            "groups), but serving runs single forward calls",
            "restore with InferenceSession.restore (which flattens "
            "pipelined checkpoints to data parallelism), or pass an "
            "unpipelined plan")
    mesh = mesh_lib.make_plan_mesh(plan)
    init_fn = (cosmoflow_lib.init_params if cfg.arch == "cosmoflow"
               else unet_lib.init_params)

    def build_params():
        return init_fn(jax.random.PRNGKey(config.seed), cfg)

    params = (jax.eval_shape(build_params) if abstract_params
              else build_params())
    sess = InferenceSession(config, cfg, mesh, plan, precision, params)
    if not abstract_params:
        sess.params = sess._cast_once(sess.params)
    return sess


class InferenceSession:
    """A compiled forward-only serving run. Build with
    ``repro.api.compile(RunConfig(mode="infer"))`` or
    ``InferenceSession.restore(checkpoint_dir)``, not directly."""

    def __init__(self, config, cfg, mesh, plan, precision, params):
        self.config: RunConfig = config
        self.cfg: ConvNetConfig = cfg
        self.mesh = mesh
        self.plan: plan_lib.ParallelPlan = plan
        self.precision: str = precision_lib.get(precision).name
        self.params = params
        self._fwd_fns: Dict[int, Any] = {}
        self._eval_fns: Dict[int, Any] = {}
        self._harnesses: list = []
        # §14: same observability surface as the training Session — a
        # session-owned Tracer activated only when config.trace asks,
        # and one MetricsRegistry every serve counter routes through
        self._close_lock = threading.Lock()
        self._closed = False
        self.tracer = trace_lib.Tracer()
        self._metrics = metrics_lib.MetricsRegistry()
        self._trace_path = (config.trace if isinstance(config.trace, str)
                            else None)
        self._exported_traces: set = set()
        if config.trace:
            trace_lib.enable(self.tracer)

    # --------------------------------------------------------- forward ----
    def _cast_once(self, params):
        """fp32 masters -> serving dtype, ONCE at load. The forward's
        per-use cast becomes the identity on the pre-cast tree, so
        values match the training eval forward bitwise."""
        return precision_lib.get(self.precision).cast_compute(params)

    def _forward_for(self, batch: int):
        """The jitted plan-sharded forward for a batch of ``batch``
        volumes (compiled once per observed size)."""
        d = self.plan.data_degree
        if batch < 1 or batch % d:
            raise ValueError(
                f"batch size {batch} does not divide over the plan's "
                f"data degree {d}; pass a positive multiple of {d}")
        fn = self._fwd_fns.get(batch)
        if fn is None:
            fn = train_step_lib.make_convnet_forward_step(
                self.cfg, self.mesh, plan=self.plan,
                use_pallas=self.config.use_pallas,
                overlap=self.config.overlap_halo,
                precision=self.precision)
            self._fwd_fns[batch] = fn
        return fn

    def predict(self, x):
        """Forward a batch of volumes: CosmoFlow returns ``(B, out_dim)``
        predictions, the U-Net per-voxel logits in the plan's level-0
        layout. ``x.shape[0]`` must be a multiple of the plan's data
        degree."""
        if self._closed:
            raise RuntimeError("InferenceSession is closed")
        x = jnp.asarray(x)
        fn = self._forward_for(int(x.shape[0]))
        with trace_lib.span("serve.forward", batch=int(x.shape[0])):
            return fn(self.params, x)

    def evaluate(self, x, y):
        """(loss, predictions) on a labeled batch — the SAME eval
        program ``Session.evaluate`` runs, so serving outputs can be
        checked bitwise against the training-side eval on one
        checkpoint."""
        if self._closed:
            raise RuntimeError("InferenceSession is closed")
        gb = int(x.shape[0])
        fn = self._eval_fns.get(gb)
        if fn is None:
            fn = train_step_lib.make_convnet_eval_step(
                self.cfg, self.mesh, global_batch=gb, plan=self.plan,
                use_pallas=self.config.use_pallas,
                overlap=self.config.overlap_halo,
                precision=self.precision)
            self._eval_fns[gb] = fn
        return fn(self.params, x, y)

    # --------------------------------------------------------- serving ----
    def serve(self, *, max_batch: int = 8, max_wait_ms: float = 2.0,
              max_queue: int = 64, workers: int = 1):
        """Start a batched serving harness over this session's forward
        (``repro.serve.harness.ServingHarness``): a bounded request
        queue, worker threads coalescing up to ``max_batch`` requests
        (waiting at most ``max_wait_ms`` to fill a batch), per-request
        futures, backpressure at ``max_queue``. The session closes its
        harnesses on ``close()``."""
        from repro.serve.harness import ServingHarness

        h = ServingHarness(self, max_batch=max_batch,
                           max_wait_ms=max_wait_ms, max_queue=max_queue,
                           workers=workers)
        self._harnesses.append(h)
        return h

    # --------------------------------------------------- introspection ----
    def telemetry(self) -> Dict[str, float]:
        """Serving counters, summed over this session's harnesses (live
        and closed): ``serve.requests`` / ``serve.batches`` completed,
        ``serve.batch_fill`` (mean real requests per forward),
        ``serve.queue_depth`` (current total), ``serve.worker_failures``
        (batches whose forward raised — surfaced on their futures), and
        the latency quantiles ``serve.latency_p50_ms`` / ``p95`` /
        ``p99``. Like the training Session, every value routes through
        the session's ``MetricsRegistry``."""
        out = {"serve.requests": 0.0, "serve.batches": 0.0,
               "serve.batch_fill": 0.0, "serve.queue_depth": 0.0,
               "serve.worker_failures": 0.0}
        lat: list = []
        fill_sum = 0.0
        for h in self._harnesses:
            s = h.stats()
            out["serve.requests"] += s["requests"]
            out["serve.batches"] += s["batches"]
            out["serve.queue_depth"] += s["queue_depth"]
            out["serve.worker_failures"] += s["worker_failures"]
            fill_sum += s["mean_fill"] * s["batches"]
            lat.extend(h.latencies_s())
        if out["serve.batches"]:
            out["serve.batch_fill"] = fill_sum / out["serve.batches"]
        for q, key in ((0.50, "serve.latency_p50_ms"),
                       (0.95, "serve.latency_p95_ms"),
                       (0.99, "serve.latency_p99_ms")):
            out[key] = _quantile_ms(lat, q)
        return self._metrics.absorb(out)

    def describe(self) -> InferReport:
        """The serving plan and the modeled forward-only per-device peak
        (``core.memory.infer_peak_bytes``) at this config's batch."""
        peak = memory_lib.infer_peak_bytes(
            self.cfg, self.plan, global_batch=self.config.global_batch,
            precision=self.precision)
        return InferReport(
            plan_name=self.plan.name, mesh_shape=dict(self.mesh.shape),
            precision=self.precision,
            param_count=self.cfg.param_count(), modeled_peak=peak)

    # ------------------------------------------------------ checkpoint ----
    @classmethod
    def restore(cls, path: str, *, data: Optional[int] = None,
                spatial: Optional[int] = None,
                global_batch: Optional[int] = None,
                precision: Optional[str] = None,
                trace=None) -> "InferenceSession":
        """Build an ``InferenceSession`` straight from a TRAINING
        checkpoint: the embedded run config is stripped of its
        training-only knobs (guard / grad_comm / checkpoint policy /
        pipeline), ONLY the ``params`` subtree is restored from disk
        (the optimizer state is never read), and the fp32 masters are
        cast to the serving dtype once at load.

        ``data=`` / ``spatial=`` re-degree the serving mesh — e.g. serve
        a checkpoint trained at 2x2 on a single device, or raise
        ``spatial`` so a volume that OOMs one device fits the group.
        Changed degrees (and pipelined training plans, which serving
        flattens to data parallelism) re-resolve the plan; unchanged
        degrees reuse the pinned training plan layout. ``path`` may be a
        retention root of ``step_<n>`` checkpoints, like
        ``Session.restore``."""
        meta_path = os.path.join(path, session_lib._META_FILE)
        if not os.path.exists(meta_path):
            for _, p in reversed(checkpoint.list_steps(path)):
                if checkpoint.validate(p):
                    return cls.restore(
                        p, data=data, spatial=spatial,
                        global_batch=global_batch, precision=precision,
                        trace=trace)
            raise FileNotFoundError(
                f"no checkpoint at {path}: neither "
                f"{session_lib._META_FILE} nor a valid step_<n> "
                f"directory")
        with open(meta_path) as f:
            meta = json.load(f)
        config = RunConfig.from_json(meta["run_config"])
        new_data = config.data if data is None else data
        new_spatial = config.spatial if spatial is None else spatial
        pinned_plan = config.plan
        keep_plan = (isinstance(pinned_plan, plan_lib.ParallelPlan)
                     and pinned_plan.n_groups == 1
                     and new_data == config.data
                     and new_spatial == config.spatial)
        config = dataclasses.replace(
            config, **_TRAIN_ONLY,
            data=new_data, spatial=new_spatial,
            plan=pinned_plan if keep_plan else "fixed",
            global_batch=(config.global_batch if global_batch is None
                          else global_batch),
            precision=(config.precision if precision is None
                       else precision),
            trace=config.trace if trace is None else trace)
        sess = _compile_infer(config, abstract_params=True)
        tree = checkpoint.restore(path, {"params": sess.params},
                                  mesh=sess.mesh)
        sess.params = sess._cast_once(tree["params"])
        return sess

    # ------------------------------------------------------- lifecycle ----
    def export_trace(self, path: Optional[str] = None) -> str:
        """Write the session's span log (serve.enqueue/batch/forward/
        reply and friends) as a Chrome/Perfetto trace; same uniquify
        rules as ``Session.export_trace``."""
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
        """Drain and join every serving harness, flush the §14 sinks,
        and deregister the tracer. Idempotent AND thread-safe: serve
        workers, a ``with`` block, and an atexit hook may all race into
        ``close()`` — exactly one performs the teardown."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for h in self._harnesses:
            h.close(drain=True)
        if self._trace_path and len(self.tracer):
            self.export_trace(self._trace_path)
        trace_lib.disable(self.tracer)

    def __enter__(self) -> "InferenceSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _quantile_ms(samples_s, q: float) -> float:
    """Nearest-rank quantile of latency samples, in milliseconds (0.0
    with no samples — the §14 Histogram keeps count/sum/min/max only,
    so serving retains raw samples for its latency contract)."""
    if not samples_s:
        return 0.0
    v = sorted(samples_s)
    idx = min(int(q * len(v)), len(v) - 1)
    return v[idx] * 1e3


__all__ = ["InferenceSession", "InferReport", "compile_infer"]
