"""Layer-wise performance model (paper §III-C).

    FP_l  = max{ Comp_l(D_main), sum_d 2*SR(D_halo_d) } + Comp_l(D_halo)
    Cost  = sum_l FP_l + max{ sum_l (BD_l + BF_l), sum_l AR_l(theta_l) }

The paper calibrates Comp from cuDNN microbenchmarks and SR/AR from
ping-pong + allreduce regressions; with no GPU here we parameterize the
same structure with hardware roofline constants + an efficiency curve
eff(voxels) that models the kernel-library inefficiency on small/sliced
domains (the effect behind the paper's 1.66x speedup at 8->16-way,
Fig. 6, and the conv1 peak-fraction drop in Table II).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ConvNetConfig


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float      # FLOP/s (per accelerator, fp32/bf16 as used)
    mem_bw: float          # B/s HBM
    link_bw: float         # B/s P2P (halo)
    ar_bw: float           # B/s allreduce effective per-rank bandwidth
    latency: float = 5e-6  # s per message
    base_eff: float = 0.45  # kernel-library fraction-of-peak on big domains
    bytes_per_elt: int = 4


V100 = Hardware("V100-16GB", peak_flops=15.7e12, mem_bw=900e9,
                link_bw=75e9, ar_bw=10e9)
TPU_V5E = Hardware("TPUv5e", peak_flops=197e12, mem_bw=819e9,
                   link_bw=50e9, ar_bw=25e9, bytes_per_elt=2)


def _eff(hw: Hardware, voxels: int) -> float:
    """Kernel efficiency falls off on small local domains (Table II)."""
    return hw.base_eff * (1.0 - math.exp(-voxels / 1.5e5))


def _sr(hw: Hardware, nbytes: float) -> float:
    return hw.latency + nbytes / hw.link_bw


def _allreduce(hw: Hardware, nbytes: float, n: int) -> float:
    if n <= 1:
        return 0.0
    return hw.latency * math.log2(n) + 2 * (n - 1) / n * nbytes / hw.ar_bw


def _reduce_scatter(hw: Hardware, nbytes: float, n: int) -> float:
    """One half of a ring allreduce (RS and AG each move (n-1)/n bytes)."""
    if n <= 1:
        return 0.0
    return hw.latency * math.log2(n) + (n - 1) / n * nbytes / hw.ar_bw


def reshard_time(hw: Hardware, nbytes: float, n: int,
                 kind: str = "all_to_all") -> float:
    """One stage-boundary reshard of a ``nbytes`` local activation over an
    ``n``-way spatial group (DESIGN.md §5).

    ``all_to_all`` (spatial->batch repartition) keeps ``1/n`` of the local
    bytes and sends the rest — the minimum for the permutation.
    ``all_gather`` (spatial->replicated, the legacy fallback) *receives*
    ``(n-1)`` x the local bytes. ``reduce_scatter`` is the all_gather's
    backward transpose. The P2P link bandwidth applies: reshards ride the
    same fabric as the halos.
    """
    if n <= 1:
        return 0.0
    lat = hw.latency * math.log2(n)
    if kind == "all_to_all":
        return lat + (n - 1) / n * nbytes / hw.link_bw
    if kind == "all_gather":
        return lat + (n - 1) * nbytes / hw.link_bw
    if kind == "reduce_scatter":
        return lat + (n - 1) / n * nbytes / hw.link_bw
    raise ValueError(f"reshard kind {kind!r}")


def opt_state_bytes(n_params: int, *, grad_comm: str = "overlap",
                    data_degree: int = 1) -> float:
    """Adam m+v in fp32 — the PR-2 accounting, shared by
    ``iteration_time`` and ``core/memory.py`` so the planner's time and
    memory objectives can never disagree on it. ZeRO-1
    (``reduce_scatter``) shards it over the data-parallel degree."""
    total = 2.0 * n_params * 4
    if grad_comm == "reduce_scatter":
        total /= max(data_degree, 1)
    return total


@dataclasses.dataclass
class ConvLayer:
    cin: int
    cout: int
    width: int      # global input width (cubic)
    stride: int
    kernel: int
    pooled: bool


def cosmoflow_layers(cfg: ConvNetConfig) -> List[ConvLayer]:
    layers, w, cin = [], cfg.input_width, cfg.in_channels
    npool = min(int(math.log2(cfg.input_width)) - 2,
                len(cfg.conv_channels))
    for i, c in enumerate(cfg.conv_channels):
        stride = 2 if i == 3 else 1
        pooled = i < npool
        layers.append(ConvLayer(cin, c, w, stride, cfg.kernel_size, pooled))
        w = w // stride // (2 if pooled else 1)
        cin = c
    return layers


def unet_layers(cfg: ConvNetConfig) -> List[ConvLayer]:
    layers, w, cin, ch = [], cfg.input_width, cfg.in_channels, \
        cfg.base_channels
    enc = []
    for _ in range(cfg.depth):
        layers.append(ConvLayer(cin, ch, w, 1, 3, False))
        layers.append(ConvLayer(ch, 2 * ch, w, 1, 3, True))
        enc.append(2 * ch)
        cin, ch, w = 2 * ch, 2 * ch, w // 2
    layers.append(ConvLayer(cin, ch, w, 1, 3, False))
    layers.append(ConvLayer(ch, 2 * ch, w, 1, 3, False))
    up = 2 * ch
    for skip in reversed(enc):
        w *= 2
        layers.append(ConvLayer(up, skip, w, 1, 2, False))        # deconv
        layers.append(ConvLayer(2 * skip, skip, w, 1, 3, False))
        layers.append(ConvLayer(skip, skip, w, 1, 3, False))
        up = skip
    return layers


def conv_net_flops_per_sample(cfg: ConvNetConfig, forward_only=False) -> float:
    """Analytic conv FLOPs/sample (must reproduce paper Table I)."""
    k3 = cfg.kernel_size ** 3
    total = 0.0
    if cfg.arch == "cosmoflow":
        w, cin = cfg.input_width, cfg.in_channels
        npool = min(int(math.log2(w)) - 2, len(cfg.conv_channels))
        for i, c in enumerate(cfg.conv_channels):
            ow = w // 2 if i == 3 else w
            total += 2 * k3 * cin * c * ow ** 3
            w = ow // 2 if i < npool else ow
            cin = c
    else:
        w, cin, ch = cfg.input_width, cfg.in_channels, cfg.base_channels
        enc = []
        for _ in range(cfg.depth):
            total += 2 * k3 * cin * ch * w ** 3
            total += 2 * k3 * ch * 2 * ch * w ** 3
            enc.append(2 * ch)
            cin, ch, w = 2 * ch, 2 * ch, w // 2
        total += 2 * k3 * cin * ch * w ** 3
        total += 2 * k3 * ch * 2 * ch * w ** 3
        up_in = 2 * ch
        for skip in reversed(enc):
            w *= 2
            total += 2 * 8 * up_in * skip * w ** 3  # deconv
            total += 2 * k3 * 2 * skip * skip * w ** 3
            total += 2 * k3 * skip * skip * w ** 3
            up_in = skip
        total += 2 * up_in * cfg.out_dim * w ** 3
    return total if forward_only else 3.0 * total  # fwd + bwd-data + bwd-filter


def _layer_fp_time(hw: Hardware, l: ConvLayer, ways: int,
                   per_gpu_batch: float,
                   overlap: bool = True,
                   act_bytes: Optional[int] = None) -> Tuple[float, float]:
    """Returns (fp_time, comp_time_only) for one forward conv.

    ``overlap=True`` is the paper's model — the halo transfer hides behind
    the interior compute: ``max{Comp(D_main), halo} + Comp(D_halo)``.
    ``overlap=False`` models the serialized exchange-then-conv lowering
    (the repo's legacy blocking path): ``Comp(D_main) + halo + Comp(D_halo)``
    — the two modes bracket what the runtime can do, and their gap is the
    predicted win of the interior/boundary decomposition.
    """
    out_w = l.width // l.stride
    local_vox = out_w ** 3 / max(ways, 1)
    flops = 2 * l.kernel ** 3 * l.cin * l.cout * out_w ** 3 / max(ways, 1) \
        * per_gpu_batch
    comp_main = flops / (hw.peak_flops * _eff(hw, int(local_vox)))
    if ways > 1 and l.width // ways >= 1:
        halo_elems = (l.kernel - l.stride) * (l.width // l.stride) ** 2 \
            * l.cin * per_gpu_batch
        halo_bytes = max(halo_elems, 0) * (act_bytes or hw.bytes_per_elt)
        halo_time = 2 * _sr(hw, halo_bytes)
        # halo-region compute: one boundary plane each side
        halo_flops = 2 * l.kernel ** 3 * l.cin * l.cout \
            * (l.width // l.stride) ** 2 * max(l.kernel - l.stride, 0) \
            * per_gpu_batch
        comp_halo = halo_flops / (hw.peak_flops * _eff(hw, int(local_vox)))
        if overlap:
            fp = max(comp_main, halo_time) + comp_halo
        else:
            fp = comp_main + halo_time + comp_halo
    else:
        fp = comp_main
    return fp, comp_main


def _scheduled_fp_times(
    cfg: ConvNetConfig,
    hw: Hardware,
    layers: List[ConvLayer],
    schedule: Sequence[str],
    *,
    num_gpus: int,
    ways: int,
    global_batch: int,
    overlap: bool,
    remat_schedule: Optional[Sequence[bool]] = None,
    act_bytes: Optional[int] = None,
) -> Tuple[float, float, float]:
    """(fp_total, bp_total, reshard_total) under a per-layer parallelism
    ``schedule`` (DESIGN.md §5): each entry is the layer's layout —
    ``"spatial"`` (the ``ways``-way depth partition), ``"batch"`` (the
    spatial group moved into the batch grid: per-device batch shrinks by
    ``ways``, no halo, no redundancy), or ``"replicated"`` (the legacy
    fallback: full per-group batch computed redundantly, no halo). For
    cosmoflow the schedule carries one trailing entry for the FC head
    (compute unpriced — the head is tiny — but its entry positions the
    CNN->FC reshard).

    Mode changes between consecutive entries are priced as stage-boundary
    reshards of the incoming activation: ``all_to_all`` when the batch
    grid is involved (both directions — the backward transpose is the
    reverse ``all_to_all``), ``all_gather`` forward + ``reduce_scatter``
    backward for spatial->replicated, and free for replicated->spatial
    (a local slice whose transpose is zero-padding).

    ``remat_schedule`` (same length) marks rematerialized entries: their
    forward is recomputed inside the backward pass, so their fp cost is
    charged to bp a second time — the recompute-for-memory trade the
    budgeted planner prices (DESIGN.md §9). ``act_bytes`` overrides the
    activation element width (2 for bf16/fp16 plans): halo and reshard
    traffic halves while gradients stay fp32.
    """
    n_entries = len(layers) + (1 if cfg.arch == "cosmoflow" else 0)
    if len(schedule) != n_entries:
        raise ValueError(
            f"schedule has {len(schedule)} entries; {cfg.arch} needs "
            f"{n_entries}")
    bad = set(schedule) - {"spatial", "batch", "replicated"}
    if bad:
        raise ValueError(f"unknown schedule modes {sorted(bad)}")
    if remat_schedule is not None and len(remat_schedule) != n_entries:
        raise ValueError(
            f"remat_schedule has {len(remat_schedule)} entries; "
            f"expected {n_entries}")
    groups = max(num_gpus // ways, 1)
    pg_group = global_batch / groups   # per-device batch, spatial/replicated
    pg_batch = global_batch / num_gpus  # per-device batch, batch layers
    # activation entering each entry: (width^3, channels); the FC entry
    # sees the final feature map
    entries: List[Tuple[Optional[ConvLayer], int, int]] = [
        (l, l.width, l.cin) for l in layers]
    if cfg.arch == "cosmoflow":
        last = layers[-1]
        w_out = last.width // last.stride // (2 if last.pooled else 1)
        entries.append((None, w_out, last.cout))

    fp_total = bp_total = reshard_total = 0.0
    prev = schedule[0]
    for k, ((l, w_in, c_in), mode) in enumerate(zip(entries, schedule)):
        if mode != prev:
            # local activation entering the boundary: spatial layout holds
            # 1/ways of the volume, batch layout 1/ways of the group batch;
            # only the replicated fallback holds the full group tensor
            local_elems = w_in ** 3 * c_in * pg_group
            if prev in ("spatial", "batch"):
                local_elems /= ways
            nbytes = local_elems * (act_bytes or hw.bytes_per_elt)
            if "batch" in (prev, mode):
                fwd = bwd = reshard_time(hw, nbytes, ways, "all_to_all")
            elif mode == "replicated":
                fwd = reshard_time(hw, nbytes, ways, "all_gather")
                bwd = reshard_time(hw, nbytes, ways, "reduce_scatter")
            else:  # replicated -> spatial: local slice / zero-pad
                fwd = bwd = 0.0
            fp_total += fwd
            bp_total += bwd
            reshard_total += fwd + bwd
            prev = mode
        if l is None:
            continue  # FC head: compute unpriced, reshard above
        if mode == "spatial":
            fp, _ = _layer_fp_time(hw, l, ways, pg_group, overlap=overlap,
                                   act_bytes=act_bytes)
        elif mode == "batch":
            fp, _ = _layer_fp_time(hw, l, 1, pg_batch, overlap=overlap,
                                   act_bytes=act_bytes)
        else:
            fp, _ = _layer_fp_time(hw, l, 1, pg_group, overlap=overlap,
                                   act_bytes=act_bytes)
        fp_total += fp
        bp_total += 2 * fp
        if remat_schedule is not None and remat_schedule[k]:
            bp_total += fp  # forward recomputed inside backward
    return fp_total, bp_total, reshard_total


def iteration_time(
    cfg: ConvNetConfig,
    hw: Hardware,
    *,
    num_gpus: int,
    ways: int,            # spatial partitioning (depth)
    global_batch: int,
    overlap: bool = True,  # False: serialized halo (blocking lowering)
    grad_comm: str = "overlap",  # DESIGN.md §4 gradient-reduction lowering
    schedule: Optional[Sequence[str]] = None,  # DESIGN.md §5 per-layer plan
    remat_schedule: Optional[Sequence[bool]] = None,  # DESIGN.md §9 remat
    act_bytes: Optional[int] = None,  # activation width (2 = bf16/fp16)
) -> Dict[str, float]:
    """Predicted seconds per training iteration (paper Eq. Cost).

    ``grad_comm`` mirrors the runtime knob: ``"overlap"`` is the paper's
    model (the allreduce hides behind backprop — the Cost equation's
    ``max``); ``"monolithic"`` serializes the whole reduction after the
    backward pass (the seed's tail-psum lowering: fp + bp + AR);
    ``"reduce_scatter"`` overlaps the RS half with backprop but pays the
    param all_gather after the optimizer, and shards Adam's (m, v) by
    the data-parallel degree (``opt_state_bytes``, ZeRO-1).

    ``schedule`` prices a per-layer parallelism plan instead of the single
    network-wide ``ways`` (see ``_scheduled_fp_times`` /
    ``core.plan.plan_schedule``): spatial layers keep the ``ways``-way
    partition, ``batch``/``replicated`` layers run unpartitioned, and
    layout changes add reshard cost terms (returned as ``"reshard"``).
    """
    layers = (cosmoflow_layers(cfg) if cfg.arch == "cosmoflow"
              else unet_layers(cfg))
    groups = max(num_gpus // ways, 1)
    per_gpu_batch = global_batch / groups
    reshard_total = 0.0
    if schedule is not None:
        fp_total, bp_total, reshard_total = _scheduled_fp_times(
            cfg, hw, layers, schedule, num_gpus=num_gpus, ways=ways,
            global_batch=global_batch, overlap=overlap,
            remat_schedule=remat_schedule, act_bytes=act_bytes)
    else:
        if remat_schedule is not None:
            raise ValueError("remat_schedule requires schedule=")
        fp_total, bp_total = 0.0, 0.0
        for l in layers:
            fp, comp = _layer_fp_time(hw, l, ways, per_gpu_batch,
                                      overlap=overlap, act_bytes=act_bytes)
            fp_total += fp
            # BD + BF ~ 2x the forward cost, same halo structure
            bp_total += 2 * fp
    n_params = cfg.param_count()
    grad_bytes = n_params * 4
    ar = _allreduce(hw, grad_bytes, num_gpus)
    opt_bytes = opt_state_bytes(n_params, grad_comm=grad_comm,
                                data_degree=groups)
    if grad_comm == "monolithic":
        gc_time, total = ar, fp_total + bp_total + ar
    elif grad_comm == "reduce_scatter":
        # mirror the runtime lowering: grads psum over the spatial group
        # (hook-overlapped) + RS over the data-parallel degree
        # (overlapped), then the param all_gather after the optimizer
        # (serialized tail). State shards by the data degree (ZeRO-1).
        spatial_ar = _allreduce(hw, grad_bytes, ways)
        half = _reduce_scatter(hw, grad_bytes, groups)
        gc_time = spatial_ar + 2 * half
        total = fp_total + max(bp_total, spatial_ar + half) + half
    else:  # "overlap"
        gc_time, total = ar, fp_total + max(bp_total, ar)
    return {
        "fp": fp_total, "bp": bp_total, "allreduce": ar,
        "grad_comm": gc_time, "opt_state_bytes": opt_bytes,
        "reshard": reshard_total,
        "total": total,
        "samples_per_s": global_batch / total,
        "per_gpu_batch": per_gpu_batch,
    }


def _plan_layer_map(
        cfg: ConvNetConfig,
        layers: List[ConvLayer]) -> List[Tuple[Tuple[int, ...], int, int]]:
    """Per *plan* layer (``core/plan.py`` indexing): the perf-layer
    indices it covers plus its entry activation ``(width, channels)``.

    cosmoflow plan layer ``i`` is conv block ``i``; the trailing FC entry
    covers no conv (compute unpriced — the head is tiny) but positions
    the CNN->FC boundary activation. A unet plan layer is a resolution
    *level*: its two encoder convs plus its deconv+2-conv decoder triple
    (the level's down and up work live on the same device group, so skip
    concats stay group-local); the last plan layer is the bottleneck."""
    if cfg.arch == "cosmoflow":
        out: List[Tuple[Tuple[int, ...], int, int]] = [
            ((i,), l.width, l.cin) for i, l in enumerate(layers)]
        last = layers[-1]
        w_out = last.width // last.stride // (2 if last.pooled else 1)
        out.append(((), w_out, last.cout))
        return out
    depth = cfg.depth
    out = []
    for lvl in range(depth):
        dec0 = 2 * depth + 2 + 3 * (depth - 1 - lvl)
        idxs = (2 * lvl, 2 * lvl + 1, dec0, dec0 + 1, dec0 + 2)
        out.append((idxs, layers[2 * lvl].width, layers[2 * lvl].cin))
    out.append(((2 * depth, 2 * depth + 1),
                layers[2 * depth].width, layers[2 * depth].cin))
    return out


def group_param_counts(
        cfg: ConvNetConfig,
        group_ranges: Sequence[Tuple[int, int]]) -> List[float]:
    """Per-group parameter counts of a pipelined split (DESIGN.md §13):
    conv kernels summed over each group's plan-layer range, with every
    non-conv parameter (FC head, BN scales, biases) charged to the plan
    layer that owns it — cosmoflow's trailing FC entry, the unet
    level-0 head. Shared by ``pipeline_iteration_time`` (per-group
    allreduce volume) and ``core/memory.py`` (per-group step state), so
    time and capacity always price the same parameter split."""
    layers = (cosmoflow_layers(cfg) if cfg.arch == "cosmoflow"
              else unet_layers(cfg))
    pmap = _plan_layer_map(cfg, layers)
    conv_params = [float(sum(layers[i].kernel ** 3 * layers[i].cin
                             * layers[i].cout for i in idxs))
                   for idxs, _, _ in pmap]
    rem = max(cfg.param_count() - sum(conv_params), 0.0)
    conv_params[-1 if cfg.arch == "cosmoflow" else 0] += rem
    return [sum(conv_params[a:b]) for a, b in group_ranges]


def pipeline_iteration_time(
    cfg: ConvNetConfig,
    hw: Hardware,
    *,
    group_ranges: Sequence[Tuple[int, int]],  # per-group plan-layer range
    data_degree: int,          # data-parallel degree WITHIN each group
    micro_batches: int,
    global_batch: int,
    schedule: str = "1f1b",
    grad_comm: str = "overlap",
    act_bytes: Optional[int] = None,
) -> Dict[str, float]:
    """Predicted seconds per iteration of a pipelined plan (DESIGN.md
    §13): ``P = len(group_ranges)`` disjoint device groups, each a pure
    ``data_degree``-way data-parallel mesh, executing ``micro_batches``
    micro-batches.

    Per-micro-batch stage time is forward + recompute-based backward
    (``4x`` the forward — the runtime re-runs each segment's forward
    inside its VJP, so pipelining never stores cross-segment residuals)
    plus the *per-group* gradient allreduce: hook-overlapped with the
    backward 3x under ``"overlap"``, serialized after it under
    ``"monolithic"``. The ``"1f1b"`` schedule keeps every group busy once
    filled — ``(M+P-1) * max_g t_g`` with bubble fraction
    ``(P-1)/(M+P-1)`` — while the ``"sequential"`` oracle blocks each
    micro-batch through all groups: ``M * sum_g t_g``. Cross-group
    boundary transfers are point-to-point sends of the per-device
    activation shard (2 directions per boundary for cosmoflow, 4 for
    unet: the decoder comes back up through every cut)."""
    layers = (cosmoflow_layers(cfg) if cfg.arch == "cosmoflow"
              else unet_layers(cfg))
    pmap = _plan_layer_map(cfg, layers)
    d = max(data_degree, 1)
    m = max(micro_batches, 1)
    p = len(group_ranges)
    per_dev = global_batch / m / d
    elt = act_bytes or hw.bytes_per_elt
    fp_layer: List[float] = []
    for idxs, _, _ in pmap:
        fp_layer.append(sum(
            _layer_fp_time(hw, layers[i], 1, per_dev,
                           act_bytes=act_bytes)[0] for i in idxs))
    group_params = group_param_counts(cfg, group_ranges)

    stage_times: List[float] = []
    ar_max = 0.0
    for (a, b), gparams in zip(group_ranges, group_params):
        fp = sum(fp_layer[a:b])
        ar = _allreduce(hw, gparams * 4, d)
        ar_max = max(ar_max, ar)
        if grad_comm == "monolithic":
            stage_times.append(4 * fp + ar)
        else:  # "overlap": hooks hide the reduce behind the 3x backward
            stage_times.append(fp + max(3 * fp, ar))
    if schedule == "sequential":
        compute = m * sum(stage_times)
    else:  # 1f1b: fill P-1, then the slowest group paces every slot
        compute = (m + p - 1) * max(stage_times)
    dirs = 2 if cfg.arch == "cosmoflow" else 4
    transfer = 0.0
    for a, _ in group_ranges[1:]:
        _, w, c = pmap[a]
        transfer += m * dirs * _sr(hw, w ** 3 * c * per_dev * elt)
    total = compute + transfer
    return {
        "total": total,
        "compute": compute,
        "transfer": transfer,
        "grad_comm": ar_max,
        "stage_times": tuple(stage_times),
        "bubble_fraction": (p - 1) / (m + p - 1),
        "samples_per_s": global_batch / total,
        "per_gpu_batch": per_dev,
    }


def memory_per_sample_bytes(cfg: ConvNetConfig,
                            batchnorm: Optional[bool] = None) -> float:
    """Activation memory per sample (fwd stores + grads), paper Table I."""
    layers = (cosmoflow_layers(cfg) if cfg.arch == "cosmoflow"
              else unet_layers(cfg))
    total = 0.0
    for l in layers:
        out_w = l.width // l.stride
        total += (l.width ** 3 * l.cin + out_w ** 3 * l.cout) * 4
    # stored activations + gradient buffers + cuDNN workspace: the single
    # factor 3.8 reproduces paper Table I across ALL sizes (0.824 / 6.59 /
    # 52.7 GiB for 128/256/512 -> we get 0.82 / 6.56 / 52.6).
    total *= 3.8
    bn = cfg.batchnorm if batchnorm is None else batchnorm
    if bn:
        total *= 2  # paper §IV: BN doubles memory requirements
    return total
