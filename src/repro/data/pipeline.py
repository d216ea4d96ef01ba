"""Spatially-parallel I/O pipeline (paper §III-B, Fig. 3).

Key ideas reproduced:
 1. *Spatial-parallel reads*: the per-device callback of
    ``jax.make_array_from_callback`` receives exactly the index slab that
    device owns under the batch+spatial sharding, and the loader reads only
    that hyperslab from the store — PFS bandwidth strong-scales with the
    spatial partitioning instead of being capped by the mini-batch size.
 2. *Distributed in-memory cache*: epoch 0 populates a (rank -> hyperslab)
    cache; epochs 1+ never touch the store. An owner map records which
    logical rank cached which hyperslab, so a cache hit served to a
    DIFFERENT rank than its owner is counted as redistribution traffic
    (the shuffle cost the paper's distributed cache pays).
 3. *Shuffle schedule*: before each epoch a permutation maps samples to
    iterations. ``schedule_for_epoch(e)`` is a pure function of
    ``(seed, e)`` — two loaders with the same seed produce identical
    schedules in any call order, which is what lets a supervisor resume
    mid-epoch and replay the exact batch sequence (DESIGN.md §12).
 4. *Halo margin reads* (``halo_voxels=``): each shard may read its
    hyperslab expanded by a voxel margin on partitioned spatial dims, so
    the bytes the first conv's halo exchange will request are already in
    the shard's cache. Reads stay hyperslab-exact: the served array is
    always the exact requested slab; only the *read* (and the cache
    entry, and the PFS byte count) covers the margin.
 5. *In-place batches*: each shard of a batch is a host buffer shaped
    like the shard, and each sample's read lands in its row — straight
    from the store when the cache is off and there is no margin
    (``IOStats.bytes_in_place``), else copied from the cache entry or
    the widened read. The loader reuses the buffers (``BatchBuffers``)
    once the arrays placed from them are ready (DESIGN.md §12).

The loader is thread-safe: a ``PrefetchLoader`` (``data/prefetch.py``)
calls ``load_batch`` from worker threads, so cache and counter mutations
take an internal lock. A "sample-parallel" baseline loader (one rank
reads the whole sample — the pre-paper state of practice) is provided
for the Fig. 5 comparison.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.store import HyperslabStore
from repro.obs import trace as trace_lib


@dataclasses.dataclass
class IOStats:
    pfs_bytes: int = 0
    cache_bytes_local: int = 0
    cache_bytes_redistributed: int = 0
    label_fetches: int = 0  # store.target() reads (not served by cache)
    bytes_in_place: int = 0  # store bytes read straight into a batch buffer

    def reset(self):
        self.pfs_bytes = self.cache_bytes_local = 0
        self.cache_bytes_redistributed = 0
        self.label_fetches = 0
        self.bytes_in_place = 0

    def cache_hit_ratio(self) -> float:
        """Fraction of loader bytes served from the distributed cache."""
        hit = self.cache_bytes_local + self.cache_bytes_redistributed
        total = hit + self.pfs_bytes
        return hit / total if total else 0.0


class BatchBuffers:
    """Host batch buffers, reused from batch to batch. ``take`` lends one
    out; ``give`` returns a batch's buffers with the array placed from
    them. A returned buffer is lent again only once that array reports
    ready (its host-to-device transfer has ended), and never when the
    array aliases it (a CPU device may place a host array without a
    copy) or its holder deleted it. Not thread-safe: the loader calls it
    under its lock."""

    def __init__(self):
        # (shape, dtype) -> [(buffer, the array placed from it)]
        self._free: Dict[Tuple, List[Tuple[np.ndarray, jax.Array]]] = {}

    def take(self, shape: Tuple[int, ...], dtype) -> np.ndarray:
        pool = self._free.get((shape, np.dtype(dtype)), [])
        # an array its holder deleted no longer says when its transfer
        # ended (and asking it would crash): its buffer leaves the pool
        pool[:] = [e for e in pool if not e[1].is_deleted()]
        for j, (buf, placed) in enumerate(pool):
            if placed.is_ready():
                del pool[j]
                return buf
        return np.empty(shape, dtype)

    def give(self, bufs: Iterable[np.ndarray], placed: jax.Array) -> None:
        bufs = list(bufs)
        if _aliases(placed, bufs):
            return
        for buf in bufs:
            self._free.setdefault((buf.shape, buf.dtype), []).append(
                (buf, placed))


def _aliases(placed: jax.Array, bufs: List[np.ndarray]) -> bool:
    """Whether a shard of ``placed`` on a host-memory (CPU) device uses
    the memory of one of ``bufs``."""
    spans = [(b.ctypes.data, b.ctypes.data + b.nbytes) for b in bufs]
    return any(lo <= shard.data.unsafe_buffer_pointer() < hi
               for shard in placed.addressable_shards
               if shard.device.platform == "cpu" for lo, hi in spans)


class SpatialParallelLoader:
    """Yields sharded global batches; each device's slab is read (or served
    from cache) independently."""

    def __init__(
        self,
        store: HyperslabStore,
        mesh,
        batch_spec: P,           # e.g. P(('data',), 'model') for (N, D, ...)
        global_batch: int,
        seed: int = 0,
        cache: bool = True,
        label_spec: Optional[P] = None,
        halo_voxels: int = 0,
    ):
        self.store = store
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, batch_spec)
        self.label_sharding = (
            NamedSharding(mesh, label_spec) if label_spec is not None else None
        )
        self.global_batch = global_batch
        self.seed = seed
        self.cache_enabled = cache
        self.halo_voxels = halo_voxels
        # cache[(sample, what, slab)] = (owner_rank, ndarray)
        self._cache: Dict[Tuple, Tuple[int, np.ndarray]] = {}
        self._label_cache: Dict[Tuple[int, ...], jax.Array] = {}
        self.stats = IOStats()
        self.epoch = 0
        self._lock = threading.Lock()
        self._buffers = BatchBuffers()  # taken and given under _lock
        self._rank_of = {d: i for i, d in enumerate(self.mesh.devices.flat)}

    # ------------------------------------------------------------ sched ----
    def schedule_for_epoch(self, epoch: int) -> np.ndarray:
        """The epoch's sample permutation as a PURE function of
        ``(seed, epoch)`` — identical across loader instances, across
        sync/prefetch wrappers, and after a mid-run resume."""
        rng = np.random.default_rng([self.seed, int(epoch)])
        return rng.permutation(self.store.num_samples)

    def epoch_schedule(self) -> np.ndarray:
        order = self.schedule_for_epoch(self.epoch)
        self.epoch += 1
        return order

    # ------------------------------------------------------------ fetch ----
    def _expand(self, slab: Tuple[slice, ...], dims: Tuple[int, ...]):
        """Widen bounded spatial slices by the halo margin (clamped)."""
        if not self.halo_voxels:
            return slab
        out = []
        for s, dim in zip(slab, dims):
            lo = 0 if s.start is None else s.start
            hi = dim if s.stop is None else s.stop
            out.append(slice(max(lo - self.halo_voxels, 0),
                             min(hi + self.halo_voxels, dim)))
        return tuple(out) + slab[len(dims):]

    def _fetch(self, sample: int, slab: Tuple[slice, ...], device_rank: int,
               what: str, out: np.ndarray) -> None:
        """Write one hyperslab into ``out`` (a row of a batch buffer),
        from the distributed cache or the store. With the cache off and
        no halo margin the store reads straight into ``out``; otherwise
        the read (and cache entry) covers the ``halo_voxels``-expanded
        slab in an array of its own, never a view of a reused buffer,
        and the exact requested slab is copied into ``out``."""
        dims = self.store.sample_shape[:3]
        wide = self._expand(slab, dims)
        key = (sample, what) + tuple((s.start, s.stop) for s in wide)
        with self._lock:
            hit = self._cache.get(key) if self.cache_enabled else None
        if hit is not None:
            owner, arr = hit
            with self._lock:
                if owner == device_rank:
                    self.stats.cache_bytes_local += arr.nbytes
                else:
                    self.stats.cache_bytes_redistributed += arr.nbytes
        elif wide is slab and not self.cache_enabled:
            with trace_lib.span("io.read") as span:
                self.store.read_hyperslab_into(sample, slab, out, what)
                span.set(bytes=out.nbytes, in_place=True)
            with self._lock:
                self.stats.pfs_bytes += out.nbytes
                self.stats.bytes_in_place += out.nbytes
            return
        else:
            with trace_lib.span("io.read") as span:
                arr = self.store.read_hyperslab(sample, wide, what)
                span.set(bytes=arr.nbytes, in_place=False)
            with self._lock:
                self.stats.pfs_bytes += arr.nbytes
                if self.cache_enabled:
                    self._cache[key] = (device_rank, arr)
        if wide is not slab:
            arr = arr[tuple(
                slice((0 if s.start is None else s.start) - w.start,
                      (0 if s.start is None else s.start) - w.start
                      + ((dim if s.stop is None else s.stop)
                         - (0 if s.start is None else s.start)))
                for s, w, dim in zip(slab, wide, dims))]
        np.copyto(out, arr)

    @staticmethod
    def _slab_key(idx: Tuple[slice, ...], shape) -> Tuple:
        """Concrete (start, stop) pairs for an index slab — normalizes
        ``slice(None)`` vs ``slice(0, dim)`` so callback indices and
        device-map indices always produce the same key."""
        return tuple(s.indices(dim)[:2] for s, dim in zip(idx, shape))

    def _rank_map(self, shape, sharding) -> Dict[Tuple, int]:
        """index-slab -> logical rank, from the sharding's device map —
        the rank that OWNS the slab a callback is filling (the cache
        owner-rank fix: rank 0 no longer claims every hyperslab)."""
        out = {}
        for dev, idx in sharding.addressable_devices_indices_map(
                tuple(shape)).items():
            out[self._slab_key(idx, shape)] = self._rank_of[dev]
        return out

    def _read_shards(self, sample_ids: np.ndarray, shape, sharding,
                     what: str) -> Tuple[Dict[Tuple, np.ndarray], int]:
        """Read each addressable device's shard of the batch into a host
        buffer shaped like the shard, each sample into its row, keyed by
        ``_slab_key``, before anything is placed; and the bytes the
        placement will move. One shard per device, in the order
        ``make_array_from_callback`` asks for them (once when the
        sharding is fully replicated). The buffers come from the
        loader's pool; ``_load_batch`` gives them back once placed."""
        ranks = self._rank_map(shape, sharding)
        devices = sharding.addressable_devices_indices_map(tuple(shape))
        indices = ([(slice(None),) * len(shape)]
                   if sharding.is_fully_replicated else devices.values())
        dtype = self.store.dtype(what)
        ready: Dict[Tuple, np.ndarray] = {}
        for idx in indices:
            # idx[0] selects samples; idx[1:4] is the spatial hyperslab.
            key = self._slab_key(idx, shape)
            slab = tuple(idx[1:]) if what == "y" else (
                tuple(idx[1:-1]) + (slice(None),))
            if key not in ready:
                with self._lock:
                    ready[key] = self._buffers.take(
                        tuple(hi - lo for lo, hi in key), dtype)
            for row, s in zip(ready[key], sample_ids[idx[0]]):
                self._fetch(int(s), slab, ranks[key], what, row)
        placed = sum(ready[self._slab_key(idx, shape)].nbytes
                     for idx in devices.values())
        return ready, placed

    def _place(self, shape, sharding, ready) -> jax.Array:
        """The host-to-device transfer of shards ``_read_shards`` made."""
        return jax.make_array_from_callback(
            shape, sharding, lambda idx: ready[self._slab_key(idx, shape)])

    def _vector_targets(self, sample_ids: np.ndarray):
        """The batch's vector regression targets: the placed array from
        the label cache, else the host array read from the store, which
        ``_place_targets`` places and caches — ``store.target`` is only
        re-read (and the batch only re-``device_put``) on a miss."""
        key = tuple(int(s) for s in sample_ids)
        if self.cache_enabled:
            with self._lock:
                hit = self._label_cache.get(key)
            if hit is not None:
                return hit
        tg = np.stack([self.store.target(int(s)) for s in sample_ids])
        with self._lock:
            self.stats.label_fetches += len(key)
        return tg

    def _place_targets(self, sample_ids: np.ndarray, tg) -> jax.Array:
        if isinstance(tg, jax.Array):  # a label-cache hit
            return tg
        y = jax.device_put(
            tg, NamedSharding(self.mesh, P(self.sharding.spec[0])))
        if self.cache_enabled:
            with self._lock:
                self._label_cache[tuple(int(s) for s in sample_ids)] = y
        return y

    # ------------------------------------------------------------ batch ----
    def load_batch(self, sample_ids: np.ndarray):
        """Build the sharded (N, D, H, W, C) global batch for these
        samples: the whole cost of one batch, on whichever thread runs
        it (a prefetch worker, or the caller)."""
        with trace_lib.span("io.load", samples=len(sample_ids)):
            return self._load_batch(sample_ids)

    def _load_batch(self, sample_ids: np.ndarray):
        """Read every shard first (``io.read`` per store read), then
        place them all inside one ``io.place`` span."""
        shape = (len(sample_ids),) + self.store.sample_shape
        xs, nbytes = self._read_shards(sample_ids, shape, self.sharding, "x")
        voxel = self.store.label_kind == "voxel" and self.label_sharding
        if voxel:
            lshape = (len(sample_ids),) + self.store.sample_shape[:-1]
            ys, ybytes = self._read_shards(sample_ids, lshape,
                                           self.label_sharding, "y")
        else:
            tg = self._vector_targets(sample_ids)
            ybytes = 0 if isinstance(tg, jax.Array) else tg.nbytes
        with trace_lib.span("io.place", bytes=nbytes + ybytes):
            x = self._place(shape, self.sharding, xs)
            y = (self._place(lshape, self.label_sharding, ys) if voxel
                 else self._place_targets(sample_ids, tg))
        with self._lock:
            self._buffers.give(xs.values(), x)
            if voxel:
                self._buffers.give(ys.values(), y)
        return x, y

    def close(self) -> None:
        """Sync loaders hold no threads; kept so every loader drains the
        same way (``PrefetchLoader.close`` is the real one)."""


class SampleParallelLoader(SpatialParallelLoader):
    """Baseline (paper Fig. 5): every sample is read IN FULL by a single
    rank and then scattered — per-rank I/O does not shrink with spatial
    parallelism. Used only by the I/O benchmark."""

    def _load_batch(self, sample_ids: np.ndarray):
        full = []
        for s in sample_ids:
            key = (int(s), "x", "full")
            with self._lock:
                hit = self._cache.get(key) if self.cache_enabled else None
            if hit is not None:
                arr = hit[1]
                with self._lock:
                    self.stats.cache_bytes_local += arr.nbytes
            else:
                with trace_lib.span("io.read") as span:
                    arr = self.store.read_full(int(s))
                    span.set(bytes=arr.nbytes)
                with self._lock:
                    self.stats.pfs_bytes += arr.nbytes
                    if self.cache_enabled:
                        self._cache[key] = (0, arr)
            full.append(arr)
        batch = np.stack(full)
        # the scatter to the spatial sharding = pure redistribution traffic
        with self._lock:
            self.stats.cache_bytes_redistributed += batch.nbytes
        tg = self._vector_targets(sample_ids)
        ybytes = 0 if isinstance(tg, jax.Array) else tg.nbytes
        with trace_lib.span("io.place", bytes=batch.nbytes + ybytes):
            x = jax.device_put(batch, self.sharding)
            y = self._place_targets(sample_ids, tg)
        return x, y
