"""Per-device activation-memory model + traced-program measurement
(DESIGN.md §9).

The paper's headline argument is about *capacity*, not just speed:
hybrid (batch+spatial) parallelism aggregates the memory of the whole
spatial group, which is what makes full-resolution 512^3 samples
trainable at all (Table I: 52.7 GiB/sample against a 16 GiB V100).
This module prices that argument so the planner (``core/plan.py``) can
optimize iteration time *subject to a memory budget* instead of
assuming every candidate fits.

Two halves:

* **Model** — ``plan_peak_bytes`` walks a ``ParallelPlan`` layer by
  layer and returns the predicted peak per-device bytes at the start of
  the backward pass (the liveness peak of reverse-mode AD): every
  layer's saved-for-backward residuals under the stage's batch/spatial
  sharding, plus params (fp32 masters + the precision policy's compute
  copy), gradients, optimizer state (PR-2's ZeRO-1 accounting), and a
  backward working-set term. A stage marked ``remat`` saves only each
  block's *input* and recomputes the internals in backward — its
  internals move from the resident sum into the transient term.

* **Measurement** — ``trace_peak_bytes`` replays the *actual traced
  program*: it runs a last-use liveness scan over the jaxpr of the real
  forward+backward (inlining ``jit``/``remat2``/``shard_map`` bodies;
  shard_map bodies carry per-device local shapes, so the result is peak
  bytes per device), taking the max over program points of live buffer
  bytes. It knows nothing of the analytic model — what jax saved for
  backward, dropout masks, BN statistics, remat recompute transients
  all fall out of the jaxpr — which makes it the validation oracle:
  ``tests/test_memory.py`` pins model-vs-measured within 15% across
  remat on/off, precisions, and plans.

The model intentionally shares its layer walk with ``perf_model`` (the
same ``cosmoflow_layers``/``unet_layers`` structure the planner prices
for time), so a plan's time and memory can never desync from each
other.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax

from repro.configs.base import ConvNetConfig
from repro.core import perf_model, precision as precision_lib
from repro.core.spatial_conv import index_pool_applies

# Structural coefficients, calibrated once against the jaxpr-liveness
# measurement over {cosmoflow W16/W32, unet} x {fp32, bf16} x {remat
# on/off} (max error 13%; tests pin model-vs-measured within 15%):
#
# _SAVED_PER_BLOCK — float residuals a conv block keeps per output-sized
# tensor beyond its input: the conv output (for the BN backward) and the
# activation output (for the pooling / next conv backward). A block whose
# max-pool takes the saved-index path keeps a uint8 per pool window in
# place of the activation output (``_saved_bytes``).
_SAVED_PER_BLOCK = 2.0
# _WORKING_SET_COPIES — concurrent output-sized copies while one block's
# forward+backward is in flight (padded conv operands, BN intermediates,
# select masks, cotangents). The liveness scans show ~4-5 copies of the
# largest block's output at the peak program point.
_WORKING_SET_COPIES = 4.25
# Every model's max-pool: window = stride = 2.
_POOL_WINDOW = 2


@dataclasses.dataclass(frozen=True)
class MemoryBreakdown:
    """Predicted peak per-device bytes, by source. ``activations`` is the
    resident saved-for-backward sum; ``workspace`` the transient max
    (backward working set, remat recompute)."""

    params: int
    param_copy: int      # low-precision compute copy (0 for fp32)
    grads: int
    opt_state: int
    activations: int
    workspace: int

    @property
    def total(self) -> int:
        """Peak bytes. Activations and gradients do NOT peak together:
        the activation peak sits in the deepest blocks' forward/backward
        (no gradients produced yet) and the gradient tree is complete
        only after the residuals have been freed — so the two compete
        under a max, while params/copies/optimizer state are resident
        throughout."""
        return (self.params + self.param_copy + self.opt_state
                + max(self.activations + self.workspace, self.grads))

    @property
    def gib(self) -> float:
        return self.total / 2 ** 30

    def describe(self) -> str:
        g = 2.0 ** 30
        return (f"total={self.total / g:.3f}GiB (act={self.activations / g:.3f}"
                f" ws={self.workspace / g:.3f} params={self.params / g:.3f}"
                f" copy={self.param_copy / g:.3f} grads={self.grads / g:.3f}"
                f" opt={self.opt_state / g:.3f})")


# --------------------------------------------------------------- model ----
def _plan_entries(cfg: ConvNetConfig, plan) -> List[Tuple[Any, Any]]:
    """(ConvLayer-or-None, Stage) per priced entry, mirroring
    ``plan.plan_schedule``'s layer->stage mapping (cosmoflow: conv blocks
    + the FC head entry; unet: encoder/bottleneck/decoder with the deconv
    charged to the deeper level's stage). Deconv entries never inherit a
    stage's ``remat`` — the runtime keeps up-convolutions outside the
    checkpointed bodies (``plan.plan_remat_schedule`` agrees), so their
    residuals must stay in the resident sum."""
    if cfg.arch == "cosmoflow":
        layers = perf_model.cosmoflow_layers(cfg)
        out = [(l, plan.stage_for(i)) for i, l in enumerate(layers)]
        out.append((None, plan.stage_for(len(layers))))
        return out
    layers = perf_model.unet_layers(cfg)

    def no_remat(st):
        return dataclasses.replace(st, remat=False) if st.remat else st

    stages = []
    for lvl in range(cfg.depth):            # encoder: 2 convs per level
        stages += [plan.stage_for(lvl)] * 2
    stages += [plan.stage_for(cfg.depth)] * 2   # bottleneck
    for lvl in reversed(range(cfg.depth)):  # decoder: deconv + 2 convs
        stages += [no_remat(plan.stage_for(lvl + 1))] \
            + [plan.stage_for(lvl)] * 2
    return list(zip(layers, stages))


def _stage_divisors(plan, st) -> Tuple[int, int]:
    """(spatial divisor of the voxel volume, batch divisor) for ``st``."""
    vox = 1
    for a in st.spatial_names:
        vox *= plan.degree(a)
    batch = 1
    for a in st.batch_axes:
        batch *= plan.degree(a)
    return vox, batch


def _index_pooled(plan, l, st) -> bool:
    """Whether conv layer ``l``'s max-pool, under stage ``st``, lowers
    through the saved window index (``spatial_conv.maxpool3d``)."""
    if not l.pooled:
        return False
    w = l.width // l.stride
    return index_pool_applies(
        [w // plan.degree(a) if a else w for a in st.spatial_axes],
        _POOL_WINDOW, _POOL_WINDOW)


def pool_index_share(cfg: ConvNetConfig, plan) -> float:
    """Share of the model's max-pools that lower through the saved window
    index under ``plan``; 0 where all fall back or there are none."""
    pooled = [_index_pooled(plan, l, st)
              for l, st in _plan_entries(cfg, plan)
              if l is not None and l.pooled]
    return sum(pooled) / len(pooled) if pooled else 0.0


def _saved_bytes(plan, l, st, act_bytes: int) -> float:
    """Bytes a conv block keeps for its backward per output element,
    beyond its input: ``_SAVED_PER_BLOCK`` activation-sized tensors, or,
    where its pool takes the saved-index path, the conv output and one
    uint8 per pool window. (A U-Net skip needs no copy of its own: the
    decoder's backward keeps the concatenation it feeds.)"""
    if _index_pooled(plan, l, st):
        return (_SAVED_PER_BLOCK - 1) * act_bytes + 1 / _POOL_WINDOW ** 3
    return _SAVED_PER_BLOCK * act_bytes


def _pair_second(cfg: ConvNetConfig, idx: int) -> bool:
    """Whether ``_plan_entries`` entry ``idx`` is the second conv of a
    U-Net conv pair, whose input a checkpointed pair recomputes."""
    if cfg.arch == "cosmoflow":
        return False
    pairs = 2 * cfg.depth + 2   # encoder levels and bottleneck
    return idx % 2 == 1 if idx < pairs else (idx - pairs) % 3 == 2


def plan_peak_bytes(
    cfg: ConvNetConfig,
    plan,
    *,
    global_batch: int,
    grad_comm: str = "overlap",
    precision: Union[str, "precision_lib.PrecisionPolicy", None] = None,
    include_optimizer: bool = True,
) -> MemoryBreakdown:
    """Predicted peak per-device bytes of one training step under
    ``plan`` (DESIGN.md §9).

    The activation peak of reverse-mode AD: every saved-for-backward
    residual resident at once, plus the working set of the block whose
    forward/backward is in flight. Per conv block the residuals are the
    block *input* (for the filter gradient) plus ``_saved_bytes`` per
    output element (conv output for the BN backward, activation output
    for the pooling backward or the pool's window index), all under the
    stage's sharding. A ``remat`` stage keeps only each block's input (a
    U-Net block is a conv pair) and re-materializes
    the internals transiently inside the backward (they move into the
    ``workspace`` term, alongside the ``_WORKING_SET_COPIES`` every
    in-flight block pays).

    ``precision`` resolves per ``core/precision.py`` (default: the
    plan's recorded policy): activations/residuals take the compute
    dtype's width, masters/grads/optimizer state stay fp32, and a
    casting policy adds a params-sized compute copy.
    """
    pol = precision_lib.get(
        precision if precision is not None
        else getattr(plan, "precision", "fp32"))
    act_bytes = pol.act_bytes
    if getattr(plan, "pipeline", None) is not None and plan.n_groups > 1:
        return _pipeline_peak_bytes(
            cfg, plan, pol, global_batch=global_batch,
            grad_comm=grad_comm, include_optimizer=include_optimizer)

    resident = 0.0   # saved-for-backward residuals
    transient = 0.0  # max recompute/backward working set
    entries = _plan_entries(cfg, plan)
    for idx, (l, st) in enumerate(entries):
        vox_div, batch_div = _stage_divisors(plan, st)
        b_local = global_batch / max(batch_div, 1)
        if l is None:
            # FC head: flattened features + the small fc intermediates
            last = perf_model.cosmoflow_layers(cfg)[-1]
            w_out = last.width // last.stride // (2 if last.pooled else 1)
            flat = w_out ** 3 * last.cout
            fc = flat + 2 * sum(cfg.fc_dims)
            resident += fc * b_local * act_bytes
            continue
        n_in = l.width ** 3 / vox_div
        n_out = (l.width // l.stride) ** 3 / vox_div
        saved_in = n_in * l.cin * b_local * act_bytes
        internals = (_saved_bytes(plan, l, st, act_bytes) * n_out * l.cout
                     * b_local)
        working = _WORKING_SET_COPIES * n_out * l.cout * b_local * act_bytes
        remat = getattr(st, "remat", False)
        if not (remat and _pair_second(cfg, idx)):
            resident += saved_in
        if remat:
            # internals recomputed transiently inside this block's remat
            # backward, on top of the block's normal working set
            transient = max(transient, working + internals)
        else:
            resident += internals
            transient = max(transient, working)

    n_params = cfg.param_count()
    params = n_params * 4                       # fp32 masters
    param_copy = n_params * act_bytes if pol.casts_params else 0
    grads = n_params * 4                        # fp32 via the cast transpose
    opt = 0
    if include_optimizer:
        entry_vox, entry_batch = _stage_divisors(plan, plan.stages[0])
        del entry_vox
        opt = int(perf_model.opt_state_bytes(
            n_params, grad_comm=grad_comm, data_degree=entry_batch))
    return MemoryBreakdown(
        params=int(params), param_copy=int(param_copy), grads=int(grads),
        opt_state=opt, activations=int(resident), workspace=int(transient))


def _pipeline_peak_bytes(
    cfg: ConvNetConfig,
    plan,
    pol: "precision_lib.PrecisionPolicy",
    *,
    global_batch: int,
    grad_comm: str,
    include_optimizer: bool,
) -> MemoryBreakdown:
    """Per-device peak of a pipelined plan (DESIGN.md §13): every device
    belongs to exactly ONE stage group, so the plan's peak is the max
    over groups, each charged only its own layer slice and its
    parameter shard of the step state (``perf_model.group_param_counts``
    — the same split the allreduce pricing uses).

    Activations follow the pipeline runtime's recompute contract: a
    node's backward rebuilds the segment vjp from the boundary input,
    so per in-flight micro-batch the resident set is the group's entry
    activation (plus, for unet down groups, the skip outputs parked
    until the decoder visit) — NOT the segment internals. The schedule
    sets the window: group ``g`` admits ``min(P-g, M)`` forwards before
    its first backward under 1F1B; the fully-drained sequential oracle
    holds one. The whole segment's internals at a single micro-batch
    reappear transiently inside the recompute backward (workspace),
    which is why pipelined memory SHRINKS with the micro-batch count —
    the capacity lever the budgeted planner trades against the bubble."""
    act_bytes = pol.act_bytes
    m = max(plan.pipeline.micro_batches, 1)
    n_grp = plan.n_groups
    sched = plan.pipeline.schedule
    entries = _plan_entries(cfg, plan)
    depth = cfg.depth if cfg.arch == "unet" else 0
    per_group: List[List[Tuple[int, Any, Any]]] = [[] for _ in range(n_grp)]
    for idx, (l, st) in enumerate(entries):
        per_group[plan.stages.index(st)].append((idx, l, st))

    group_params = perf_model.group_param_counts(
        cfg, plan.group_layer_ranges())
    best: Optional[MemoryBreakdown] = None
    for g, sub in enumerate(per_group):
        if not sub:
            continue
        vox_div, batch_div = _stage_divisors(plan, sub[0][2])
        b_micro = global_batch / m / max(batch_div, 1)
        win = 1 if sched == "sequential" else min(n_grp - g, m)
        resident = 0.0
        transient = 0.0   # segment saved set rebuilt by the recompute
        work_max = 0.0    # one block's backward working set in flight
        entry_l = sub[0][1]
        if entry_l is None:  # group owns only the FC head
            last = perf_model.cosmoflow_layers(cfg)[-1]
            w_out = last.width // last.stride // (2 if last.pooled else 1)
            resident += w_out ** 3 * last.cout * b_micro * act_bytes * win
        else:
            resident += (entry_l.width ** 3 / vox_div * entry_l.cin
                         * b_micro * act_bytes * win)
        for idx, l, st in sub:
            if l is None:
                last = perf_model.cosmoflow_layers(cfg)[-1]
                w_out = (last.width // last.stride
                         // (2 if last.pooled else 1))
                flat = w_out ** 3 * last.cout
                transient += (flat + 2 * sum(cfg.fc_dims)) \
                    * b_micro * act_bytes
                continue
            n_in = l.width ** 3 / vox_div
            n_out = (l.width // l.stride) ** 3 / vox_div
            # recompute backward: the segment's saved set at ONE micro,
            # plus the working set of whichever block is in flight
            transient += (n_in * l.cin * act_bytes
                          + _saved_bytes(plan, l, st, act_bytes)
                          * n_out * l.cout) * b_micro
            work_max = max(work_max, _WORKING_SET_COPIES * n_out
                           * l.cout * b_micro * act_bytes)
            if cfg.arch == "unet" and idx < 2 * depth and idx % 2 == 1:
                # encoder skip output: parked on the down group until
                # its decoder visit, one copy per in-flight micro
                resident += n_out * l.cout * b_micro * act_bytes * win
        n_params = group_params[g]
        params = n_params * 4
        param_copy = n_params * act_bytes if pol.casts_params else 0
        grads = n_params * 4
        opt = 0
        if include_optimizer:
            opt = int(perf_model.opt_state_bytes(
                int(n_params), grad_comm=grad_comm,
                data_degree=max(batch_div, 1)))
        cand = MemoryBreakdown(
            params=int(params), param_copy=int(param_copy),
            grads=int(grads), opt_state=opt, activations=int(resident),
            workspace=int(transient + work_max))
        if best is None or cand.total > best.total:
            best = cand
    assert best is not None
    return best


def infer_peak_bytes(
    cfg: ConvNetConfig,
    plan,
    *,
    global_batch: int,
    precision: Union[str, "precision_lib.PrecisionPolicy", None] = None,
) -> MemoryBreakdown:
    """Predicted peak per-device bytes of one forward-only serving call
    (DESIGN.md §15).

    No reverse pass means nothing is saved for backward: buffers die at
    their last use, so the transient peak is the largest single block's
    working set (input + in-flight output copies) under the stage's
    sharding — which is why per-device peak falls with spatial degree.
    Params are resident in the serving dtype only (fp32 masters are
    cast ONCE at load, so no master+copy pair coexists); there are no
    gradients and no optimizer state. U-Net skip tensors are the one
    resident term: encoder outputs parked until their decoder visit."""
    pol = precision_lib.get(
        precision if precision is not None
        else getattr(plan, "precision", "fp32"))
    act_bytes = pol.act_bytes
    resident = 0.0   # unet encoder skips parked across the descent
    working = 0.0    # largest in-flight block: input + output copies
    entries = _plan_entries(cfg, plan)
    depth = cfg.depth if cfg.arch == "unet" else 0
    for idx, (l, st) in enumerate(entries):
        vox_div, batch_div = _stage_divisors(plan, st)
        b_local = global_batch / max(batch_div, 1)
        if l is None:
            last = perf_model.cosmoflow_layers(cfg)[-1]
            w_out = last.width // last.stride // (2 if last.pooled else 1)
            fc = w_out ** 3 * last.cout + 2 * sum(cfg.fc_dims)
            working = max(working, fc * b_local * act_bytes)
            continue
        n_in = l.width ** 3 / vox_div
        n_out = (l.width // l.stride) ** 3 / vox_div
        block = (n_in * l.cin + _SAVED_PER_BLOCK * n_out * l.cout) \
            * b_local * act_bytes
        working = max(working, block)
        if cfg.arch == "unet" and idx < 2 * depth and idx % 2 == 1:
            resident += n_out * l.cout * b_local * act_bytes
    n_params = cfg.param_count()
    params = n_params * (act_bytes if pol.casts_params else 4)
    return MemoryBreakdown(
        params=int(params), param_copy=0, grads=0, opt_state=0,
        activations=int(resident), workspace=int(working))


def data_parallel_peak_bytes(
    cfg: ConvNetConfig,
    *,
    global_batch: int,
    num_gpus: int = 1,
    grad_comm: str = "overlap",
    precision: Union[str, None] = "fp32",
) -> MemoryBreakdown:
    """Peak per-device bytes under PURE data parallelism (the paper's
    baseline that OOMs at full resolution): spatial degree 1, the batch
    split ``num_gpus`` ways, no remat."""
    from repro.core import plan as plan_lib  # local import: no cycle

    plan = plan_lib.uniform_plan(
        cfg, spatial_axes=("model", None, None), spatial_degrees=(1, 1, 1),
        data_degrees=(num_gpus,))
    return plan_peak_bytes(cfg, plan, global_batch=global_batch,
                           grad_comm=grad_comm, precision=precision)


# --------------------------------------------- traced-program liveness ----
_SUBJAXPR_PRIMS = {
    "jit", "remat2", "closed_call", "custom_jvp_call", "custom_vjp_call",
    "shard_map",
}


def _eqn_subjaxprs(eqn) -> List[Any]:
    if eqn.primitive.name not in _SUBJAXPR_PRIMS:
        return []
    out = []
    for v in eqn.params.values():
        name = type(v).__name__
        if name == "ClosedJaxpr":
            out.append(v.jaxpr)
        elif name == "Jaxpr":
            out.append(v)
    return out


def _var_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * jax.numpy.dtype(dtype).itemsize


def _is_var(v) -> bool:
    return hasattr(v, "aval") and type(v).__name__ not in ("Literal",)


def _jaxpr_peak(jaxpr) -> int:
    """Max-over-program-points live bytes of a linearly executed jaxpr.

    Buffers die at their last textual use (the trace order is a valid
    schedule); an eqn's outputs and its still-live inputs coexist. For
    eqns carrying sub-jaxprs the inner peak is measured recursively and
    superimposed on the outer live set minus the eqn's own inputs (the
    sub-jaxpr counts those as its invars — same buffers)."""
    eqns = jaxpr.eqns
    last_use = {}
    for idx, eqn in enumerate(eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = idx
    for v in jaxpr.outvars:
        if _is_var(v):
            last_use[v] = len(eqns)  # escapes: never dies here

    live = {}
    for v in tuple(jaxpr.constvars) + tuple(jaxpr.invars):
        if _is_var(v):
            live[v] = _var_bytes(v)
    cur = sum(live.values())
    peak = cur
    for idx, eqn in enumerate(eqns):
        subs = _eqn_subjaxprs(eqn)
        if subs:
            inner = max(_jaxpr_peak(s) for s in subs)
            inv = sum(live[v] for v in {v for v in eqn.invars if _is_var(v)}
                      if v in live)
            peak = max(peak, cur - inv + inner)
        add = 0
        for v in eqn.outvars:
            if type(v).__name__ == "DropVar" or not _is_var(v):
                continue
            if v not in live:
                live[v] = _var_bytes(v)
                add += live[v]
        cur += add
        peak = max(peak, cur)
        for v in {v for v in eqn.invars if _is_var(v)}:
            if last_use.get(v) == idx and v in live:
                cur -= live.pop(v)
    return peak


def _find_shard_map(jaxpr, depth: int = 0):
    """First shard_map body reachable through jit wrappers (its shapes
    are per-device local)."""
    if depth > 4:
        return None
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            for v in eqn.params.values():
                if type(v).__name__ == "Jaxpr":
                    return v
                if type(v).__name__ == "ClosedJaxpr":
                    return v.jaxpr
        if eqn.primitive.name == "jit":
            sub = _find_shard_map(eqn.params["jaxpr"].jaxpr, depth + 1)
            if sub is not None:
                return sub
    return None


def trace_peak_bytes(fn, *args, per_device: bool = True) -> int:
    """Measured peak bytes of ``fn(*args)``: trace to a jaxpr and run the
    liveness scan. With ``per_device=True`` (default) and a ``shard_map``
    in the program, the scan runs on the shard_map *body*, whose shapes
    are per-device local — the number a device's HBM actually sees."""
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    if per_device:
        body = _find_shard_map(jaxpr)
        if body is not None:
            jaxpr = body
    return _jaxpr_peak(jaxpr)


__all__ = [
    "MemoryBreakdown", "plan_peak_bytes", "infer_peak_bytes",
    "data_parallel_peak_bytes", "trace_peak_bytes",
]
