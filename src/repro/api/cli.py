"""Shared CLI plumbing for the example drivers: argparse flags that map
one-to-one onto ``RunConfig`` fields, so every driver exposes the same
knobs and the only assembly path is ``repro.api.compile``.

(Replaces the pre-§10 ``repro.launch.planner_cli``, which resolved plans
driver-side and still left each example threading six kwargs.)
"""
from __future__ import annotations

import dataclasses
import os

from repro.api.config import RunConfig

# the checkout's root: src/repro/api/cli.py -> three levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a driver process
    and return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set,
    is left to JAX, which reads it itself; otherwise the cache lives at
    the fixed path ``<checkout>/.jax_cache`` (the path is part of the
    cache key, so it must not move between runs). Call it from a
    driver's setup, before the first compile — never from a library
    module or a test."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_session_args(ap) -> None:
    """The standard Session knobs. ``--model`` keeps its historical
    meaning (the spatial degree on the mesh's ``model`` axis)."""
    ap.add_argument("--steps", type=int, default=None,
                    help="override the preset's total_steps")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the preset's global_batch")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel degree")
    ap.add_argument("--model", type=int, default=1,
                    help="spatial-parallel degree (mesh 'model' axis)")
    ap.add_argument("--pipeline", type=int, default=1, metavar="P",
                    help="pipeline-parallel degree (DESIGN.md §13): split "
                         "the layer chain into P stages on disjoint device "
                         "groups; --data stays the TOTAL data degree")
    ap.add_argument("--micro-batches", type=int, default=4, metavar="M",
                    help="micro-batches per step when --pipeline > 1")
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    choices=("1f1b", "sequential"),
                    help="1F1B interleaving, or the blocking GPipe-style "
                         "oracle (equivalence baseline)")
    ap.add_argument("--plan", action="store_true",
                    help="let the cost model pick a per-stage parallelism "
                         "plan (DESIGN.md §5) instead of the fixed degree")
    ap.add_argument("--memory-budget", type=float, default=None,
                    metavar="GIB",
                    help="per-device budget: the planner argmins time over "
                         "(boundary x kind x remat x precision) subject to "
                         "the §9 memory model fitting this")
    ap.add_argument("--precision", default=None,
                    choices=("fp32", "bf16", "fp16"),
                    help="mixed-precision policy (default: fp32, or the "
                         "budgeted plan's choice)")
    ap.add_argument("--grad-comm", default=None,
                    choices=("monolithic", "overlap", "reduce_scatter"),
                    help="gradient-reduction lowering (DESIGN.md §4)")
    ap.add_argument("--grad-clip", type=float, default=None,
                    metavar="NORM",
                    help="global grad-norm clip (0 disables; pipelined "
                         "runs need 0 — no cross-group global norm)")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint directory (final save; restore with "
                         "Session.restore)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the run to PATH "
                         "on Session.close (open at ui.perfetto.dev; "
                         "DESIGN.md §14)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append one JSON metrics row per step to PATH")


def add_serve_args(ap) -> None:
    """The batched-serving harness knobs (DESIGN.md §15), mapping
    one-to-one onto ``InferenceSession.serve`` kwargs."""
    ap.add_argument("--max-batch", type=int, default=8, metavar="B",
                    help="coalesce up to B queued requests per forward")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    metavar="MS",
                    help="max time a worker waits to fill a batch before "
                         "running a partial one")
    ap.add_argument("--max-queue", type=int, default=64, metavar="N",
                    help="bounded request queue: submit() blocks "
                         "(backpressure) once N requests are waiting")
    ap.add_argument("--workers", type=int, default=1,
                    help="serving worker threads")


def harness_kwargs(args) -> dict:
    """Parsed ``add_serve_args`` flags -> ``InferenceSession.serve``
    kwargs."""
    return {"max_batch": args.max_batch, "max_wait_ms": args.max_wait_ms,
            "max_queue": args.max_queue, "workers": args.workers}


def config_from_args(base: RunConfig, args) -> RunConfig:
    """Apply parsed ``add_session_args`` flags over a preset config.
    This is the training drivers' setup step, so it also turns on the
    persistent compilation cache (``use_compile_cache``)."""
    use_compile_cache()
    over = {"data": args.data, "spatial": args.model,
            "pipeline": args.pipeline, "micro_batches": args.micro_batches,
            "pipeline_schedule": args.pipeline_schedule}
    if args.steps is not None:
        over["total_steps"] = args.steps
    if args.batch is not None:
        over["global_batch"] = args.batch
    if args.plan or args.memory_budget is not None:
        over["plan"] = "auto"
    if args.memory_budget is not None:
        over["memory_budget_gib"] = args.memory_budget
    if args.precision:
        over["precision"] = args.precision
    if args.grad_comm:
        over["grad_comm"] = args.grad_comm
    if args.grad_clip is not None:
        over["grad_clip"] = args.grad_clip
    if args.ckpt:
        over["checkpoint_dir"] = args.ckpt
    if args.trace:
        over["trace"] = args.trace
    if args.metrics:
        over["metrics_jsonl"] = args.metrics
    return dataclasses.replace(base, **over)
