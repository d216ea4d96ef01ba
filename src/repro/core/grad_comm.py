"""Gradient-communication subsystem (DESIGN.md §4).

The seed train step reduced gradients with one tree-wide ``lax.psum``
AFTER ``value_and_grad`` returned — every reduction byte waited on the
last backward FLOP, serializing the data-parallel allreduce behind the
whole backward pass. The paper's cost model only reaches its headline
scaling when the allreduce hides behind backprop:

    Cost = Σ_l FP_l + max{ Σ_l (BD_l + BF_l), Σ_l AR_l(θ_l) }

This module restores the ``max``: per-layer reduction *hooks* — identity
``custom_vjp`` wrappers whose backward rule psums the cotangent — fire as
each layer's gradient is produced during backpropagation. The emitted
collectives depend only on that layer's cotangent, never on the rest of
the backward pass, so XLA's latency-hiding scheduler is free to run them
under the remaining backward compute (the interior/boundary trick of
DESIGN.md §3, applied to gradients instead of halos).

Three lowerings, selected by ``flags.grad_comm`` or the per-builder
``grad_comm=`` knob (``train/train_step.py``):

* ``monolithic`` — the seed's tail psum; kept as the equivalence oracle.
* ``overlap`` (default) — per-layer hooks + bucketing. Leaves below
  ``BucketPolicy.small_thresh_elems`` (BN scales/biases, FC biases) are
  coalesced in flatten order into flat buckets closed at
  ``target_bucket_bytes``, so ONE psum amortizes the per-collective
  latency over many tiny tensors; big conv/FC kernels keep their own
  hook at their use site, next to their layer's backward.
* ``reduce_scatter`` — ZeRO-1: each bucket's gradient is
  ``psum_scatter``-sharded over the data axes, the optimizer updates only
  the local 1/N shard of its state, and updated params are
  ``all_gather``-ed back. Optimizer-state memory drops by the
  data-parallel degree; spatial-axis reduction still uses the overlapped
  hooks.

Equivalence contract: all three modes produce the same updated params up
to fp32 reduction order (psum and psum_scatter+all_gather reassociate the
same sum; the CPU backend reproduces ≤1e-5 after multiple steps —
``tests/test_grad_comm.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compat
from repro.obs import trace as trace_lib

MODES = ("monolithic", "overlap", "reduce_scatter")


# ------------------------------------------------------ bucketing policy --
@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """Size-targeted coalescing: latency-bound leaves share a flat bucket.

    ``small_thresh_elems``: leaves below this (128 KiB fp32 default) are
    bandwidth-trivial — their collective cost is pure latency, so they
    coalesce. ``target_bucket_bytes``: a flat bucket closes once it holds
    this much, bounding how long the earliest-ready gradient waits for
    its bucket-mates.
    """

    small_thresh_elems: int = 1 << 15
    target_bucket_bytes: int = 4 << 20

    def is_small(self, size: int) -> bool:
        return size < self.small_thresh_elems


_POLICY = BucketPolicy()


def get_policy() -> BucketPolicy:
    return _POLICY


@contextlib.contextmanager
def bucket_policy(**kw):
    """Override the process-wide policy (tests/benches). Must wrap BOTH
    step building and tracing — the plan is resolved at trace time."""
    global _POLICY
    old = _POLICY
    _POLICY = dataclasses.replace(old, **kw)
    try:
        yield _POLICY
    finally:
        _POLICY = old


@dataclasses.dataclass(frozen=True)
class Bucket:
    indices: Tuple[int, ...]  # leaf positions, jax.tree flatten order
    shapes: Tuple[Tuple[int, ...], ...]
    dtype: Any
    flat: bool  # True: small leaves, reduced as one concatenated vector

    @property
    def size(self) -> int:
        return sum(int(math.prod(s)) if s else 1 for s in self.shapes)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Static partition of a param tree's leaves into reduction buckets."""

    buckets: Tuple[Bucket, ...]
    n_leaves: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def padded_size(self, bucket: Bucket, shards: int) -> int:
        return -(-bucket.size // shards) * shards


def make_plan(tree, policy: Optional[BucketPolicy] = None) -> Plan:
    """Partition leaves: big leaves get their own bucket (own hook at the
    use site); small leaves coalesce, in flatten order, into flat buckets
    closed at ``target_bucket_bytes`` (or on a dtype change)."""
    policy = policy or _POLICY
    leaves = jax.tree.leaves(tree)
    buckets: List[Bucket] = []
    pend: List[int] = []
    pend_shapes: List[Tuple[int, ...]] = []
    pend_bytes = 0
    pend_dtype = None

    def flush():
        nonlocal pend, pend_shapes, pend_bytes, pend_dtype
        if pend:
            buckets.append(
                Bucket(tuple(pend), tuple(pend_shapes), pend_dtype, True))
        pend, pend_shapes, pend_bytes, pend_dtype = [], [], 0, None

    for i, leaf in enumerate(leaves):
        shape = tuple(leaf.shape)
        size = int(math.prod(shape)) if shape else 1
        dt = jnp.dtype(leaf.dtype)
        if policy.is_small(size):
            if pend and dt != pend_dtype:
                flush()
            pend.append(i)
            pend_shapes.append(shape)
            pend_dtype = dt
            pend_bytes += size * dt.itemsize
            if pend_bytes >= policy.target_bucket_bytes:
                flush()
        else:
            buckets.append(Bucket((i,), (shape,), dt, False))
    flush()
    return Plan(tuple(buckets), len(leaves))


# ------------------------------------------------- per-layer hooks (vjp) --
@functools.lru_cache(maxsize=None)
def _psum_hook(axes: Tuple[str, ...]):
    @jax.custom_vjp
    def ident(x):
        return x

    @jax.named_scope("grad_comm")
    def bwd(_, g):
        return (lax.psum(g, axes),)

    ident.defvjp(lambda x: (x, None), bwd)
    return ident


@functools.lru_cache(maxsize=None)
def _bucket_psum_hook(axes: Tuple[str, ...], n: int):
    """Joint identity over a bucket's n leaves whose VJP concatenates the
    cotangents, psums the flat vector ONCE, and splits it back. The
    primal is a pure identity (XLA elides it) — concat/split live only in
    the backward pass, so the forward never pays for the coalescing and
    the transpose never materializes per-leaf zero-padded buckets."""

    @jax.custom_vjp
    def ident(*xs):
        return tuple(xs)

    @jax.named_scope("grad_comm")
    def bwd(_, gs):
        flat = lax.psum(jnp.concatenate([g.reshape(-1) for g in gs]), axes)
        out, off = [], 0
        for g in gs:
            k = g.size
            out.append(flat[off:off + k].reshape(g.shape))
            off += k
        return tuple(out)

    ident.defvjp(lambda *xs: (tuple(xs), None), bwd)
    return ident


def mark_gradient(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Tag one tensor: its gradient is allreduced over ``axes`` as soon
    as its backward contribution is complete (a per-layer hook). Identity
    in the primal; no-op when ``axes`` is empty."""
    axes = tuple(a for a in axes if a)
    if not axes:
        return x
    return _psum_hook(axes)(x)


class GradMarker:
    """Threads the hooks through model code at layer boundaries.

    ``begin(params)`` (model entry) concatenates each flat bucket of
    small leaves into one vector, hooks the vector, and splits it back —
    in backward, one psum fires once the bucket's last member (first in
    forward order) has its cotangent. ``mark(x)`` (each layer boundary)
    hooks big leaves at their use site, so the reduction is emitted next
    to that layer's backward. Both are identity when ``axes`` is empty;
    every param the model consumes must flow through one of the two, or
    its gradient misses the reduction (the equivalence tests pin this).
    """

    def __init__(self, axes: Sequence[str],
                 policy: Optional[BucketPolicy] = None):
        self.axes = tuple(a for a in axes if a)
        self.policy = policy or _POLICY
        self._pending: dict = {}  # id(leaf) -> leaf index, big leaves only

    def begin(self, tree):
        if not self.axes:
            return tree
        plan = make_plan(tree, self.policy)
        # §14 trace-time marker: hooks are emitted while jax traces the
        # model, so the observable is the reduction STRUCTURE (how many
        # buckets/leaves this program reduces), not per-step wall time —
        # the in-graph psums themselves are priced by the perf model and
        # measured by the grad_comm probe.
        trace_lib.instant("trace.grad_comm.begin",
                          buckets=plan.num_buckets, leaves=plan.n_leaves,
                          axes=",".join(self.axes))
        trace_lib.count("grad_comm.buckets", plan.num_buckets)
        leaves, treedef = jax.tree.flatten(tree)
        out = list(leaves)
        for b in plan.buckets:
            if not b.flat:
                self._pending[id(leaves[b.indices[0]])] = b.indices[0]
                continue
            hooked = _bucket_psum_hook(self.axes, len(b.indices))(
                *(leaves[i] for i in b.indices))
            for i, v in zip(b.indices, hooked):
                out[i] = v
        return jax.tree.unflatten(treedef, out)

    def mark(self, x: jax.Array) -> jax.Array:
        if not self.axes:
            return x
        size = int(math.prod(x.shape)) if x.shape else 1
        if self.policy.is_small(size):
            return x  # coalesced and hooked by begin()
        self._pending.pop(id(x), None)
        trace_lib.count("grad_comm.marks")  # big-leaf hooks emitted
        return mark_gradient(x, self.axes)

    def assert_all_marked(self) -> None:
        """Call at the end of forward: every big leaf from ``begin`` must
        have flowed through ``mark``, or its gradient would silently stay
        an unreduced per-device partial."""
        if self._pending:
            raise AssertionError(
                "grad_comm: big param leaves never passed through "
                f"GradMarker.mark (flatten indices {sorted(self._pending.values())}) "
                "— their gradients would miss the reduction")


# ------------------------------------------- reduce-scatter (ZeRO-1) path --
def _flat_bucket(leaves, b: Bucket) -> jax.Array:
    if len(b.indices) == 1:
        return leaves[b.indices[0]].reshape(-1)
    return jnp.concatenate([leaves[i].reshape(-1) for i in b.indices])


def _num_shards(data_axes: Sequence[str]) -> int:
    n = 1
    for ax in data_axes:
        n *= compat.axis_size(ax)
    return n


def shard_index(data_axes: Sequence[str]) -> jax.Array:
    """Combined (major-first) index over the data axes — matches both the
    sequential ``psum_scatter`` chunk layout and ``P(tuple(data_axes))``."""
    idx = jnp.zeros((), jnp.int32)
    for ax in data_axes:
        idx = idx * compat.axis_size(ax) + lax.axis_index(ax)
    return idx


def _pad_to(flat: jax.Array, padded: int) -> jax.Array:
    pad = padded - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


@jax.named_scope("grad_comm")
def reduce_scatter_grads(grads, plan: Plan, data_axes: Sequence[str]):
    """Bucket-flatten local grads; ``psum_scatter`` each bucket over the
    data axes so shard i holds the fully reduced chunk i. Returns a tuple
    of per-bucket fp32 shard vectors (padded to the shard grid)."""
    n = _num_shards(data_axes)
    leaves = jax.tree.leaves(grads)
    out = []
    for b in plan.buckets:
        flat = _pad_to(_flat_bucket(leaves, b).astype(jnp.float32),
                       plan.padded_size(b, n))
        for ax in data_axes:
            flat = lax.psum_scatter(flat, ax, scatter_dimension=0,
                                    tiled=True)
        out.append(flat)
    return tuple(out)


def param_shards(params, plan: Plan, data_axes: Sequence[str]):
    """Slice the local 1/N shard of each (replicated) flat param bucket."""
    n = _num_shards(data_axes)
    idx = shard_index(data_axes)
    leaves = jax.tree.leaves(params)
    out = []
    for b in plan.buckets:
        padded = plan.padded_size(b, n)
        flat = _pad_to(_flat_bucket(leaves, b), padded)
        shard_len = padded // n
        out.append(lax.dynamic_slice(flat, (idx * shard_len,), (shard_len,)))
    return tuple(out)


@jax.named_scope("grad_comm")
def all_gather_params(shards, plan: Plan, data_axes: Sequence[str],
                     template):
    """Inverse of the scatter: gather updated shards over the data axes,
    strip the padding, and rebuild the param tree."""
    leaves, treedef = jax.tree.flatten(template)
    out = list(leaves)
    for b, flat in zip(plan.buckets, shards):
        for ax in reversed(tuple(data_axes)):
            flat = lax.all_gather(flat, ax, axis=0, tiled=True)
        off = 0
        for i, shape in zip(b.indices, b.shapes):
            n = int(math.prod(shape)) if shape else 1
            out[i] = flat[off:off + n].reshape(shape).astype(leaves[i].dtype)
            off += n
    return jax.tree.unflatten(treedef, out)


def sharded_update(optimizer, grads, opt_state, params, plan: Plan,
                   data_axes: Sequence[str]):
    """ZeRO-1 step: scatter grads, update the local optimizer-state shard,
    gather updated params. ``opt_state`` must come from
    ``init_sharded_opt_state`` (per-bucket flat vectors, dim 0 sharded
    over the data axes by the caller's shard_map specs)."""
    g_shards = reduce_scatter_grads(grads, plan, data_axes)
    p_shards = param_shards(params, plan, data_axes)
    new_shards, new_state = optimizer.update(
        g_shards, opt_state, p_shards, norm_axes=tuple(data_axes))
    return all_gather_params(new_shards, plan, data_axes, params), new_state


def init_sharded_opt_state(optimizer, plan: Plan, *, num_shards: int):
    """Host-side: optimizer state over GLOBAL padded flat fp32 buckets.
    Passed through a shard_map with dim-0 ``P(data_axes)`` specs, each
    device materializes only its 1/num_shards slice — the ZeRO-1 memory
    win."""
    dummy = tuple(
        jnp.zeros((plan.padded_size(b, num_shards),), jnp.float32)
        for b in plan.buckets)
    return optimizer.init(dummy)
