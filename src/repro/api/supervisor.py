"""Auto-resume training supervisor (DESIGN.md §11).

``run(config, steps)`` wraps the §10 ``Session`` lifecycle in the
recovery loop a multi-day hybrid-parallel campaign needs: it drives
guarded steps with a wall-clock watchdog, checkpoints into a
keep-last-K retention root, and on ANY failure — an injected fault, a
hung step, a corrupt checkpoint, a persistent store error, a diverging
loss — resumes from the newest checkpoint that still validates.

Recovery is a state machine over three failure classes:

* **transient** (I/O error past the store's own retries, a stalled
  step caught by the watchdog, a ``DeviceLost`` with no count change):
  restore the newest valid checkpoint at the SAME degrees and replay.
  Replay is deterministic — batches are a pure function of the step
  index — so the post-recovery loss trajectory and params are
  bitwise-identical to an uninterrupted run (the §11 verify gate).
* **divergence** (``divergence_patience`` consecutive guard-skipped or
  non-finite-loss steps): roll back to the last checkpoint. Useful when
  the cause is transient (a bad batch window, an injected NaN burst);
  a deterministic permanent cause will re-diverge and exhaust
  ``max_restarts`` rather than loop forever.
* **elastic** (``DeviceLost(available=k)``): the §5/§9 planner is
  re-invoked at degrees feasible for ``k`` devices (spatial halved
  until it fits and divides the volume, data shrunk to the largest
  batch divisor), and state is re-placed onto the smaller mesh: params
  transfer exactly; ZeRO-1 flat bucket optimizer state is re-padded for
  the new shard count (exact — padding is trailing zeros); an
  incompatible layout (e.g. precision change) resets the optimizer and
  says so in the report.

Everything the recovery machinery did is returned as a
``SupervisorReport`` — per-step losses, restart/resume/rollback/replan
counts, recovery wall-times — so the resilience bench can plot recovery
time against checkpoint interval.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.config import RunConfig, RunConfigError
from repro.api.session import _META_FILE, Session, _compile
from repro.api.session import compile as api_compile
from repro.core import faults
from repro.core import plan as plan_lib
from repro.obs import trace as trace_lib
from repro.train import checkpoint

_MIN_LOCAL_WIDTH = 4  # the §5 over-decomposition floor


class StepTimeout(RuntimeError):
    """A step exceeded the supervisor's watchdog budget."""


class Divergence(RuntimeError):
    """Too many consecutive skipped / non-finite-loss steps."""


class SupervisorError(RuntimeError):
    """The supervisor exhausted ``max_restarts`` and gave up."""


@dataclasses.dataclass
class SupervisorReport:
    """What happened: the trajectory plus every recovery the loop took."""

    steps: int
    losses: List[float]
    restarts: int = 0        # failures handled (any class)
    resumes: int = 0         # checkpoint restores (incl. rollbacks)
    cold_starts: int = 0     # fresh compiles (no usable checkpoint)
    rollbacks: int = 0       # divergence-triggered restores
    replans: int = 0         # elastic degree changes
    skipped_steps: int = 0   # guard-vetoed updates over the final session
    recovery_s: List[float] = dataclasses.field(default_factory=list)
    events: List[str] = dataclasses.field(default_factory=list)
    final_data: int = 0
    final_spatial: int = 0
    session: Optional[Session] = dataclasses.field(default=None, repr=False)


def _default_batch_fn(config: RunConfig) -> Callable[[int], Tuple]:
    """Deterministic synthetic batches: a pure function of (seed, step),
    so replay after a resume feeds the exact bytes the failed run saw."""
    cfg = config.resolve_model()
    w, gb = cfg.input_width, config.global_batch

    def make(t: int):
        key = jax.random.fold_in(jax.random.PRNGKey(config.seed + 101), t)
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (gb, w, w, w, cfg.in_channels),
                              jnp.float32)
        if cfg.arch == "cosmoflow":
            y = jax.random.normal(ky, (gb, cfg.out_dim), jnp.float32)
        else:
            y = jax.random.randint(ky, (gb, w, w, w), 0, cfg.out_dim)
        return x, y

    return make


def _loader_batch_fn(sess: Session, config: RunConfig) -> Callable[[int], Tuple]:
    """Batches from the session's (possibly prefetching) loader over
    ``config.data_dir``, as a pure function of ``t``: step ``t`` is
    chunk ``t % bpe`` of the pure ``schedule_for_epoch(t // bpe)``
    permutation, so a resumed run replays the exact batch sequence the
    failed run saw — bitwise, sync or prefetch (DESIGN.md §12). Rebuilt
    per session: the loader (and its worker threads) die with the
    session on every restart."""
    loader = sess.make_loader(config.data_dir)
    gb = config.global_batch
    bpe = loader.store.num_samples // gb  # batches per epoch
    if bpe < 1:
        raise RunConfigError(
            "data_dir",
            f"dataset has {loader.store.num_samples} samples < "
            f"global_batch={gb}", "add samples or shrink the batch")

    def make(t: int):
        epoch, b = divmod(t, bpe)
        order = loader.schedule_for_epoch(epoch)
        return loader.load_batch(order[b * gb:(b + 1) * gb])

    return make


def degrade_config(config: RunConfig, available: int) -> RunConfig:
    """Feasible degrees for a shrunken device count: halve spatial until
    it fits ``available`` and still divides the volume above the §5
    width floor, then give data the largest remaining degree that
    divides the global batch. A pinned ``ParallelPlan`` is dropped back
    to the ``"auto"`` policy so the planner re-argmins at the new mesh."""
    if available < 1:
        raise SupervisorError(f"no devices left (available={available})")
    cfg = config.resolve_model()
    spatial = max(config.spatial, 1)
    while spatial > 1 and (
            spatial > available or cfg.input_width % spatial
            or cfg.input_width // spatial < _MIN_LOCAL_WIDTH):
        spatial //= 2
    data = max(available // spatial, 1)
    while config.global_batch % data:
        data -= 1
    plan = ("auto" if isinstance(config.plan, plan_lib.ParallelPlan)
            else config.plan)
    return dataclasses.replace(config, data=data, spatial=spatial, plan=plan)


def _adapt_opt_state(old, new_template):
    """Re-place a restored optimizer state onto a new session's layout.
    Returns ``(state, reset)``. Identical layouts pass through; 1-D flat
    leaves of different length are the ZeRO-1 bucket states, whose
    padding is trailing zeros — truncate/zero-extend to the new padded
    size (exact). Any structural mismatch resets to the fresh state."""
    old_leaves, old_def = jax.tree.flatten(old)
    new_leaves, new_def = jax.tree.flatten(new_template)
    if old_def != new_def:
        return new_template, True
    out = []
    for o, n in zip(old_leaves, new_leaves):
        o = jnp.asarray(o)
        if o.shape == n.shape:
            out.append(o.astype(n.dtype))
        elif o.ndim == 1 and n.ndim == 1:
            ln = n.shape[0]
            v = o[:ln]
            if ln > o.shape[0]:
                v = jnp.concatenate(
                    [v, jnp.zeros((ln - o.shape[0],), o.dtype)])
            out.append(v.astype(n.dtype))
        else:
            return new_template, True
    return jax.tree.unflatten(new_def, out), False


def _elastic_restore(path: str, new_config: RunConfig,
                     report: SupervisorReport) -> Session:
    """Resume a checkpoint saved at DIFFERENT degrees: rebuild the old
    run abstractly (structure only) to read the tree, compile the new
    session, and transfer params + adapted optimizer state."""
    with open(os.path.join(path, _META_FILE)) as f:
        old_config = RunConfig.from_json(json.load(f)["run_config"])
    template = _compile(old_config, abstract_state=True)
    tree = checkpoint.restore(
        path, {"params": template.params, "opt": template.opt_state})
    sess = api_compile(new_config)
    sess.params = jax.tree.map(jnp.asarray, tree["params"])
    sess.opt_state, reset = _adapt_opt_state(tree["opt"], sess.opt_state)
    if reset:
        report.events.append(
            f"optimizer state reset at step {checkpoint.latest_step(path)}"
            " (layout incompatible across the replan)")
    sess._t = checkpoint.latest_step(path)
    return sess


def _start_session(cfg_now: RunConfig, root: str,
                   report: SupervisorReport, verbose: bool) -> Session:
    found = checkpoint.latest_valid_step(root)
    if found is None:
        sess = api_compile(cfg_now)
        report.cold_starts += 1
        _event(report, verbose, "cold start at step 0 "
               f"(data={cfg_now.data} spatial={cfg_now.spatial})")
    else:
        step, path = found
        with open(os.path.join(path, _META_FILE)) as f:
            saved = RunConfig.from_json(json.load(f)["run_config"])
        if (saved.data, saved.spatial) == (cfg_now.data, cfg_now.spatial):
            sess = Session.restore(path)  # the bitwise path
        else:
            sess = _elastic_restore(path, cfg_now, report)
        report.resumes += 1
        _event(report, verbose, f"resumed from step {step} "
               f"(data={cfg_now.data} spatial={cfg_now.spatial})")
    sess.resumes = report.resumes
    return sess


def _event(report: SupervisorReport, verbose: bool, msg: str) -> None:
    report.events.append(msg)
    # §14: supervisor lifecycle (cold start / resume / replan / failure)
    # lands in whichever trace is active at that moment — failure events
    # fire BEFORE sess.close() disables the dying session's tracer, so a
    # restarted run's trace file starts clean at its own cold start.
    trace_lib.instant("supervisor.event", msg=msg)
    if verbose:
        print(f"[supervisor] {msg}")


def run(config: RunConfig, steps: int, *,
        batch_fn: Optional[Callable[[int], Tuple]] = None,
        save_every: Optional[int] = None,
        keep_last: Optional[int] = None,
        max_restarts: int = 8,
        watchdog_timeout_s: Optional[float] = None,
        divergence_patience: Optional[int] = None,
        verbose: bool = False) -> SupervisorReport:
    """Train ``config`` for ``steps`` steps under the recovery loop.

    ``batch_fn(t)`` supplies the global batch for step ``t`` and MUST be
    a pure function of ``t`` for bitwise replay (the default synthetic
    source is; with ``config.data_dir`` set the default instead streams
    the store through ``Session.make_loader`` — async per
    ``config.prefetch`` — which is equally pure in ``t``). ``save_every``/``keep_last`` default to the config's
    policy (else every ``max(1, steps // 4)`` steps, keep 3).
    ``watchdog_timeout_s`` bounds one step's wall time — a ``comm.stall``
    beyond it is treated as a failure (each session's first TWO steps
    are exempt: they pay jit compiles, which would otherwise re-trip
    the watchdog after every restart). ``divergence_patience`` rolls
    back to the last checkpoint after that many consecutive
    skipped/non-finite steps. The final session rides along on the
    report (``report.session``) for inspection; close it when done."""
    if config.checkpoint_dir is None:
        raise RunConfigError(
            "checkpoint_dir", "the supervisor recovers from checkpoints "
            "but has nowhere to write them",
            "set RunConfig.checkpoint_dir to a retention root")
    config.validate()
    root = config.checkpoint_dir
    save_every = save_every or config.save_every or max(1, steps // 4)
    keep_last = keep_last or config.keep_last or 3
    # the Session must not ALSO auto-save: the supervisor owns the
    # retention root so intervals and GC stay consistent across resumes
    cfg_now = dataclasses.replace(config, save_every=None, keep_last=None)
    loader_mode = batch_fn is None and config.data_dir is not None
    if batch_fn is None and not loader_mode:
        batch_fn = _default_batch_fn(config)

    report = SupervisorReport(
        steps=steps, losses=[float("nan")] * steps,
        final_data=config.data, final_spatial=config.spatial)
    sess: Optional[Session] = None
    pending: Optional[Tuple[float, int]] = None  # (t_fail_wall, fail_step)
    consec_bad = 0
    prev_skipped = 0.0

    while True:
        try:
            if sess is None:
                sess = _start_session(cfg_now, root, report, verbose)
                if loader_mode:
                    batch_fn = _loader_batch_fn(sess, cfg_now)
                prev_skipped = sess._skipped()
                # the first step pays the jit compile: no watchdog
                warming = 1
            while sess.step_count < steps:
                t = sess.step_count
                t0 = time.perf_counter()
                loss = float(sess.step(batch_fn(t)))  # sync: watchdog
                dt = time.perf_counter() - t0
                if (watchdog_timeout_s is not None and warming == 0
                        and dt > watchdog_timeout_s):
                    raise StepTimeout(
                        f"step {t} took {dt:.2f}s > watchdog "
                        f"{watchdog_timeout_s:.2f}s")
                warming = max(warming - 1, 0)
                report.losses[t] = loss
                if pending is not None and sess.step_count > pending[1]:
                    report.recovery_s.append(time.perf_counter()
                                             - pending[0])
                    pending = None
                skipped = sess._skipped() if config.resolved_guard else 0.0
                consec_bad = (consec_bad + 1
                              if skipped > prev_skipped
                              or not math.isfinite(loss) else 0)
                prev_skipped = skipped
                if (divergence_patience is not None
                        and consec_bad >= divergence_patience):
                    consec_bad = 0
                    raise Divergence(
                        f"{divergence_patience} consecutive skipped/"
                        f"non-finite steps ending at step {t}")
                if (t + 1) % save_every == 0 or (t + 1) == steps:
                    sess.save(checkpoint.step_dir(root, t + 1))
                    checkpoint.gc_steps(root, keep_last)
            break
        except (faults.InjectedFault, StepTimeout, Divergence,
                checkpoint.CheckpointError, OSError) as e:
            fail_step = sess.step_count if sess is not None else 0
            report.restarts += 1
            _event(report, verbose,
                   f"failure at step {fail_step}: {type(e).__name__}: {e}")
            if report.restarts > max_restarts:
                raise SupervisorError(
                    f"gave up after {max_restarts} restarts "
                    f"(last failure at step {fail_step}: {e})") from e
            if isinstance(e, faults.DeviceLost) and e.available is not None:
                cfg_now = degrade_config(cfg_now, e.available)
                report.replans += 1
                report.final_data = cfg_now.data
                report.final_spatial = cfg_now.spatial
                _event(report, verbose,
                       f"replanned for {e.available} devices: "
                       f"data={cfg_now.data} spatial={cfg_now.spatial}")
            if isinstance(e, Divergence):
                report.rollbacks += 1
            if pending is None:
                pending = (time.perf_counter(), fail_step)
            if sess is not None:
                sess.close()
            sess = None

    report.skipped_steps = int(sess.telemetry()["skipped_steps"])
    report.session = sess
    return report


__all__ = ["run", "SupervisorReport", "SupervisorError", "StepTimeout",
           "Divergence", "degrade_config"]
