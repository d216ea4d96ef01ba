"""CosmoFlow network (paper Table I), hybrid-parallel.

Faithful to the extended model of §IV: n = log2(W)-2 conv blocks with
channels (16,32,64,128,256,256,256), 3^3 SAME convs (stride 1 except block
4 which is stride 2), stride-2 pooling after each conv, optional batch-norm
after every conv, leaky-ReLU, then FC 2048 -> 256 -> 4 with dropout
(keep=0.8), no conv biases (paper removed them for performance), MSE loss.

Written in local-shard style: call inside ``jax.shard_map``. The layout of
every block is dictated by a ``ParallelPlan`` (DESIGN.md §5): each stage
names the mesh axes sharding the batch and D/H/W dims, and stage
boundaries are lowered by ``core/reshard.py`` (``all_to_all`` batch
repartition or the legacy replicated gather). Callers that pass only a
``SpatialPartitioning`` get the legacy single-degree plan — spatial
everywhere, over-decomposed dims gathered once their static local width
drops below 4 voxels, replicated FC head — derived by
``plan.legacy_convnet_plan`` from the same static width bookkeeping the
old forward pass carried inline.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ConvNetConfig
from repro.core import dist_norm, flags, grad_comm, reshard
from repro.core import plan as plan_lib
from repro.core import precision as precision_lib
from repro.core.spatial_conv import (
    SpatialPartitioning,
    conv3d,
    maxpool3d,
)

Params = Dict[str, jax.Array]


def num_blocks(cfg: ConvNetConfig) -> int:
    """All variants keep the full 7-conv stack (paper Table I: 9.44M params
    for every input size)."""
    return len(cfg.conv_channels)


def num_pools(cfg: ConvNetConfig) -> int:
    """Paper §IV: pool6 is inserted for the 256^3/512^3 models and pool7
    for 512^3 — i.e. the first log2(W)-2 blocks are pooled."""
    return min(int(math.log2(cfg.input_width)) - 2, num_blocks(cfg))


def init_params(key: jax.Array, cfg: ConvNetConfig, dtype=jnp.float32) -> Params:
    n = num_blocks(cfg)
    chans = list(cfg.conv_channels[:n])
    params: Params = {}
    cin = cfg.in_channels
    k = cfg.kernel_size
    keys = jax.random.split(key, n + len(cfg.fc_dims) + 1)
    for i, c in enumerate(chans):
        fan_in = k ** 3 * cin
        params[f"conv{i}_w"] = jax.random.normal(
            keys[i], (k, k, k, cin, c), dtype
        ) * jnp.asarray(math.sqrt(2.0 / fan_in), dtype)
        if cfg.batchnorm:
            params[f"bn{i}_scale"] = jnp.ones((c,), dtype)
            params[f"bn{i}_bias"] = jnp.zeros((c,), dtype)
        cin = c
    w = cfg.input_width
    npool = num_pools(cfg)
    for i in range(n):
        if i == 3:
            w //= 2  # stride-2 conv in block 4
        if i < npool:
            w //= 2
    flat = chans[-1] * w ** 3
    dims = list(cfg.fc_dims) + [cfg.out_dim]
    for j, dout in enumerate(dims):
        params[f"fc{j}_w"] = jax.random.normal(
            keys[n + j], (flat, dout), dtype
        ) * jnp.asarray(math.sqrt(1.0 / flat), dtype)
        params[f"fc{j}_b"] = jnp.zeros((dout,), dtype)
        flat = dout
    return params


def _resolve_plan(
    cfg: ConvNetConfig,
    plan: Optional[plan_lib.ParallelPlan],
    part: Optional[SpatialPartitioning],
    spatial_shards: Sequence[int],
) -> plan_lib.ParallelPlan:
    if plan is not None:
        return plan
    return plan_lib.legacy_convnet_plan(
        cfg, part if part is not None else SpatialPartitioning(),
        spatial_shards)


def forward(
    params: Params,
    x: jax.Array,
    cfg: ConvNetConfig,
    part: Optional[SpatialPartitioning] = None,
    *,
    plan: Optional[plan_lib.ParallelPlan] = None,
    bn_axes: Sequence[str] = (),
    spatial_shards: Sequence[int] = (1, 1, 1),
    train: bool = False,
    dropout_rng: Optional[jax.Array] = None,
    sample_ids: Optional[jax.Array] = None,  # global ids of local samples
    use_pallas: bool = False,
    overlap: Optional[bool] = None,  # None -> flags.get("overlap_halo")
    grad_axes: Sequence[str] = (),  # per-layer grad-reduction hooks (§4)
    reshard_oracle: bool = False,  # all_gather+slice instead of all_to_all
    precision=None,  # None -> the plan's policy (core/precision.py, §9)
) -> jax.Array:
    """x: local shard (N_loc, D_loc, H_loc, W_loc, Cin) -> (N_loc', out_dim).

    ``plan`` drives the per-stage layout; when None, ``part`` +
    ``spatial_shards`` select the legacy fixed-degree plan (with its
    over-decomposition gathers — paper §V-B observes 16 GPUs/sample
    already over-decomposes the deep layers). The output batch is the
    FINAL stage's local batch: plans whose CNN->FC transition repartitions
    the spatial group into the batch grid return ``N_loc / spatial_size``
    rows per device, each sample exactly once across the mesh.

    Rematerialization (DESIGN.md §9): a conv block is lowered through
    ``jax.checkpoint`` when its stage sets ``remat``; a plan with NO
    per-stage remat falls back to the global ``flags.remat`` knob for
    every block. Params are marked for gradient reduction OUTSIDE the
    checkpointed body so the §4 hooks keep firing per layer.

    ``precision`` (or the plan's recorded policy) casts the param compute
    copies and the input to the policy's compute dtype; the caller's
    ``params`` stay the fp32 masters.
    """
    plan = _resolve_plan(cfg, plan, part, spatial_shards)
    policy = precision_lib.get(
        precision if precision is not None else plan.precision)
    # compute-copy casting happens at each USE site, after the §4 grad
    # hook: the hook wraps the fp32 master, the cast sits between hook
    # and consumer, so cotangents are upcast BEFORE the cross-device
    # psum — gradient reductions always run fp32, whatever the policy.
    cst = ((lambda t: t.astype(policy.compute_dtype))
           if policy.casts_params else (lambda t: t))
    n = num_blocks(cfg)
    npool = num_pools(cfg)
    # DESIGN.md §4: big kernels get their reduction hook at the layer
    # boundary (marker.mark); BN scales/biases and FC biases are coalesced
    # into flat buckets once, here at entry (marker.begin). No-op when
    # grad_axes is empty (eval, monolithic oracle).
    marker = grad_comm.GradMarker(grad_axes)
    params = marker.begin(params)
    h = x
    if policy.casts_params and jnp.issubdtype(h.dtype, jnp.floating):
        h = h.astype(policy.compute_dtype)
    plan_remat = plan.uses_remat
    ids = sample_ids
    if ids is None and train and dropout_rng is not None:
        ids = jnp.arange(h.shape[0])
    cur = plan.stage_for(0)
    for i in range(n):
        with jax.named_scope(f"block{i}"):
            st = plan.stage_for(i)
            if st != cur:
                h, ids = reshard.apply(h, cur, st, sample_ids=ids,
                                       oracle=reshard_oracle)
                cur = st
            # block 4 (0-indexed 3) is the strided conv
            stride = 2 if i == 3 else 1
            w = cst(marker.mark(params[f"conv{i}_w"]))
            bn_params = ((cst(marker.mark(params[f"bn{i}_scale"])),
                          cst(marker.mark(params[f"bn{i}_bias"])))
                         if cfg.batchnorm else ())

            def block(h, w, *bn, _part=cur.part, _stride=stride,
                      _pool=i < npool):
                with jax.named_scope("conv"):
                    h = conv3d(h, w, _part, stride=_stride,
                               use_pallas=use_pallas, overlap=overlap)
                with jax.named_scope("norm"):
                    if bn:
                        # leaky-ReLU folded into the normalize pass (fused
                        # Pallas kernel under use_pallas) — one HBM
                        # round-trip, not two.
                        h = dist_norm.distributed_batchnorm(
                            h, bn[0], bn[1], bn_axes,
                            use_pallas=use_pallas, activation_slope=0.01)
                    else:
                        h = jax.nn.leaky_relu(h, negative_slope=0.01)
                if _pool:
                    with jax.named_scope("pool"):
                        h = maxpool3d(h, _part, window=2, stride=2,
                                      overlap=overlap)
                return h

            if st.remat if plan_remat else flags.get("remat"):
                block = jax.checkpoint(block)
            h = block(h, w, *bn_params)
    with jax.named_scope("head"):
        # CNN -> FC stage boundary: the plan picks the batch repartition
        # (all_to_all, no redundant compute) or the replicated gather
        # (the legacy fallback — FC then runs redundantly on every
        # spatial shard).
        fc_stage = plan.stage_for(n)
        if fc_stage != cur:
            h, ids = reshard.apply(h, cur, fc_stage, sample_ids=ids,
                                   oracle=reshard_oracle)
        h = _fc_head(params, h, cfg, marker.mark, cst, train, dropout_rng,
                     ids)
    marker.assert_all_marked()
    return h


def _fc_head(params: Params, h: jax.Array, cfg: ConvNetConfig, mark, cst,
             train: bool, dropout_rng: Optional[jax.Array],
             ids: Optional[jax.Array]) -> jax.Array:
    """Flatten, then FC layers with leaky-ReLU and dropout. Dropout masks
    are per (sample, layer) and deterministic: identical across every
    shard that computes a given sample (replicated FC heads agree;
    repartitioned FC heads each own distinct samples) and invariant to
    the mesh shape, the plan and a pipeline's split. ``ids`` are the
    global ids of the local rows (``None``: their positions)."""
    h = h.reshape(h.shape[0], -1)
    n_fc = len(cfg.fc_dims) + 1
    for j in range(n_fc):
        h = (h @ cst(mark(params[f"fc{j}_w"]))
             + cst(mark(params[f"fc{j}_b"])))
        if j < n_fc - 1:
            h = jax.nn.leaky_relu(h, negative_slope=0.01)
            if train and dropout_rng is not None:
                keep = 0.8
                layer_rng = jax.random.fold_in(dropout_rng, j)

                def mask_row(sid):
                    return jax.random.bernoulli(
                        jax.random.fold_in(layer_rng, sid), keep,
                        (h.shape[1],))

                row_ids = ids if ids is not None else jnp.arange(h.shape[0])
                mask = jax.vmap(mask_row)(row_ids)
                h = jnp.where(mask, h / keep, 0.0)
    return h


def segment_param_names(cfg: ConvNetConfig, start: int, stop: int):
    """Parameter names plan layers ``[start, stop)`` consume — the subset
    a pipeline device group owns (DESIGN.md §13). Plan layer ``n_blocks``
    is the FC head."""
    n = num_blocks(cfg)
    names = []
    for i in range(start, min(stop, n)):
        names.append(f"conv{i}_w")
        if cfg.batchnorm:
            names += [f"bn{i}_scale", f"bn{i}_bias"]
    if stop > n:
        for j in range(len(cfg.fc_dims) + 1):
            names += [f"fc{j}_w", f"fc{j}_b"]
    return tuple(names)


def forward_range(
    params: Params,
    h: jax.Array,
    cfg: ConvNetConfig,
    start: int,
    stop: int,
    *,
    bn_axes: Sequence[str] = (),
    train: bool = False,
    dropout_rng: Optional[jax.Array] = None,
    sample_ids: Optional[jax.Array] = None,
    grad_axes: Sequence[str] = (),
    precision=None,
) -> jax.Array:
    """Plan layers ``[start, stop)`` in pure data-parallel layout — one
    pipeline group's segment (DESIGN.md §13). ``params`` holds exactly
    the segment's subset (``segment_param_names``); there is no spatial
    partitioning and no resharding inside a group, so the body is the
    same math as the matching slice of ``forward`` with every layout
    trivial. ``sample_ids`` are the GLOBAL row ids of the local
    micro-batch rows, so the per-(sample, layer) dropout masks equal the
    no-pipeline plan's bit for bit."""
    policy = precision_lib.get(precision if precision is not None
                               else "fp32")
    cst = ((lambda t: t.astype(policy.compute_dtype))
           if policy.casts_params else (lambda t: t))
    n = num_blocks(cfg)
    npool = num_pools(cfg)
    marker = grad_comm.GradMarker(grad_axes)
    params = marker.begin(params)
    if policy.casts_params and jnp.issubdtype(h.dtype, jnp.floating):
        h = h.astype(policy.compute_dtype)
    part = SpatialPartitioning()  # group-local: no spatial axes
    for i in range(start, min(stop, n)):
        with jax.named_scope(f"block{i}"):
            stride = 2 if i == 3 else 1
            w = cst(marker.mark(params[f"conv{i}_w"]))
            with jax.named_scope("conv"):
                h = conv3d(h, w, part, stride=stride)
            with jax.named_scope("norm"):
                if cfg.batchnorm:
                    h = dist_norm.distributed_batchnorm(
                        h, cst(marker.mark(params[f"bn{i}_scale"])),
                        cst(marker.mark(params[f"bn{i}_bias"])), bn_axes,
                        activation_slope=0.01)
                else:
                    h = jax.nn.leaky_relu(h, negative_slope=0.01)
            if i < npool:
                with jax.named_scope("pool"):
                    h = maxpool3d(h, part, window=2, stride=2)
    if stop > n:
        with jax.named_scope("head"):
            h = _fc_head(params, h, cfg, marker.mark, cst, train,
                         dropout_rng, sample_ids)
    marker.assert_all_marked()
    return h


def mse_loss(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    cfg: ConvNetConfig,
    part: Optional[SpatialPartitioning] = None,
    *,
    plan: Optional[plan_lib.ParallelPlan] = None,
    bn_axes: Sequence[str] = (),
    global_batch: int = 0,
    spatial_size: int = 1,
    spatial_shards: Sequence[int] = (1, 1, 1),
    train: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    sample_ids: Optional[jax.Array] = None,
    use_pallas: bool = False,
    overlap: Optional[bool] = None,
    grad_axes: Sequence[str] = (),
    reshard_oracle: bool = False,
    precision=None,
) -> jax.Array:
    """LOCAL loss contribution, normalized so that ``psum`` over ALL mesh
    axes yields the global mean loss *and* correct grads.

    Predictions are cast up to fp32 before the squared error whatever
    ``precision`` the network computed in: the loss, its cotangent seed,
    and the gradient accumulation all run fp32 (DESIGN.md §9).

    The normalizer is the plan's ``loss_redundancy``: how many devices
    compute each sample's FC head. Replicated-gather plans (and the
    legacy path, where the caller passes ``spatial_size``) divide by the
    spatial group size — the all_gather transpose reduce-scatters the n
    redundant cotangents; batch-repartition plans have redundancy 1 and
    slice ``y`` to the local chunk alongside the activations. See
    train/train_step.py.
    """
    if plan is not None:
        redundancy = plan.loss_redundancy
        with jax.named_scope("loss"):
            y = reshard.shard_batch(y, plan.batch_extension_axes)
    else:
        redundancy = spatial_size
    pred = forward(
        params, x, cfg, part, plan=plan, bn_axes=bn_axes, train=train,
        spatial_shards=spatial_shards,
        dropout_rng=dropout_rng, sample_ids=sample_ids,
        use_pallas=use_pallas, overlap=overlap, grad_axes=grad_axes,
        reshard_oracle=reshard_oracle, precision=precision,
    )
    with jax.named_scope("loss"):
        n_global = global_batch or x.shape[0]
        per_sample = jnp.mean(jnp.square(pred.astype(jnp.float32) - y),
                              axis=-1)
        return jnp.sum(per_sample) / (n_global * redundancy)
