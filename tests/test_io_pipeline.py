"""Async input pipeline (DESIGN.md §12): prefetch-vs-sync bitwise
equivalence, schedule determinism, worker-thread fault propagation,
owner-rank cache accounting, halo margin reads, and the supervisor's
loader-backed bitwise kill-and-resume."""
import dataclasses
import tempfile
import threading

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import compat, faults
from repro.data import pipeline, prefetch, store, synthetic
from repro.data.store import StoreReadError


def _dataset(tmp, n=8, w=16, channels=2, seed=0):
    cubes, targets = synthetic.make_cosmology_dataset(
        n, w, channels=channels, seed=seed)
    store.write_dataset(tmp, cubes, targets)
    return tmp


def _mesh11():
    return compat.make_mesh((1, 1), ("data", "model"))


SPEC = P("data", "model", None, None, None)


def _loader(root, *, seed=0, cache=True, pf=0, global_batch=4, halo=0,
            throttle=None):
    ld = pipeline.SpatialParallelLoader(
        store.HyperslabStore(root, throttle_mbps=throttle), _mesh11(), SPEC,
        global_batch=global_batch, seed=seed, cache=cache, halo_voxels=halo)
    return prefetch.PrefetchLoader(ld, depth=pf) if pf else ld


# ------------------------------------------------------------ schedules ----
def test_schedule_deterministic_across_instances(tmp_path):
    root = _dataset(str(tmp_path))
    a, b = _loader(root, seed=7), _loader(root, seed=7)
    for _ in range(3):
        assert np.array_equal(a.epoch_schedule(), b.epoch_schedule())
    # pure in (seed, epoch): a THIRD instance replays epoch 1 directly,
    # without stepping through epoch 0 — the mid-epoch-resume property
    c = _loader(root, seed=7)
    assert np.array_equal(c.schedule_for_epoch(1), a.schedule_for_epoch(1))
    assert not np.array_equal(a.schedule_for_epoch(0),
                              a.schedule_for_epoch(1))


def test_schedule_identical_sync_vs_prefetch(tmp_path):
    root = _dataset(str(tmp_path))
    sync, pf = _loader(root, seed=3), _loader(root, seed=3, pf=2)
    for _ in range(2):
        assert np.array_equal(sync.epoch_schedule(), pf.epoch_schedule())
    pf.close()


# ------------------------------------------------- bitwise equivalence ----
def test_prefetch_batches_bitwise_equal_sync(tmp_path):
    root = _dataset(str(tmp_path))
    sync, pf = _loader(root, seed=5), _loader(root, seed=5, pf=2)
    for _ in range(2):  # two shuffled epochs
        o1, o2 = sync.epoch_schedule(), pf.epoch_schedule()
        for lo in range(0, 8, 4):
            xs, ys = sync.load_batch(o1[lo:lo + 4])
            xp, yp = pf.load_batch(o2[lo:lo + 4])
            assert np.array_equal(np.asarray(xs), np.asarray(xp))
            assert np.array_equal(np.asarray(ys), np.asarray(yp))
    assert pf.queue_hits > 0  # the sequential loop was actually predicted
    pf.close()


def test_prefetch_fallback_on_unpredicted_ids(tmp_path):
    """Arbitrary (non-sequential) requests stay correct — they fall back
    to a synchronous inner load and resync the predictor."""
    root = _dataset(str(tmp_path))
    sync, pf = _loader(root, seed=1), _loader(root, seed=1, pf=2)
    ids = np.array([6, 0, 3, 5])
    xs, _ = sync.load_batch(ids)
    xp, _ = pf.load_batch(ids)
    assert np.array_equal(np.asarray(xs), np.asarray(xp))
    assert pf.sync_fallbacks == 1
    # resync: after the fallback, the canonical loop predicts again
    order = pf.epoch_schedule()
    pf.load_batch(order[:4])
    pf.load_batch(order[4:8])
    assert pf.queue_hits >= 1
    pf.close()


def test_prefetch_queue_occupancy_and_telemetry(tmp_path):
    root = _dataset(str(tmp_path))
    pf = _loader(root, seed=0, pf=2)
    order = pf.epoch_schedule()
    for lo in range(0, 8, 4):
        pf.load_batch(order[lo:lo + 4])
    assert 0.0 < pf.queue_occupancy() <= 2.0
    assert pf.stall_s >= 0.0
    assert pf.served == 2
    pf.close()


def test_prefetch_close_drains_and_raises(tmp_path):
    root = _dataset(str(tmp_path))
    pf = _loader(root, pf=2)
    order = pf.epoch_schedule()
    pf.load_batch(order[:4])
    pf.close()
    pf.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        pf.load_batch(order[:4])


# ------------------------------------------------- fault propagation ----
def test_worker_thread_fault_surfaces_on_consumer(tmp_path):
    """A persistent loader.read fault fires inside the prefetch worker
    and must surface as StoreReadError on the consumer's load_batch —
    not die silently in the thread."""
    root = _dataset(str(tmp_path))
    pf = _loader(root, pf=2, cache=False)
    try:
        with faults.active(faults.FaultSpec("loader.read",
                                            probability=1.0)):
            order = pf.epoch_schedule()
            with pytest.raises(StoreReadError):
                pf.load_batch(order[:4])
    finally:
        pf.close()


def test_worker_thread_transient_fault_absorbed(tmp_path):
    """A bounded transient is absorbed by the store's retry loop inside
    the worker; the consumer sees a clean batch and the retry counter."""
    root = _dataset(str(tmp_path))
    sync = _loader(root, seed=2, cache=False)
    ref_order = sync.epoch_schedule()
    ref, _ = sync.load_batch(ref_order[:4])
    pf = _loader(root, seed=2, pf=2, cache=False)
    try:
        with faults.active(faults.FaultSpec("loader.read",
                                            at_calls=(0, 1),
                                            max_fires=2)):
            order = pf.epoch_schedule()
            x, _ = pf.load_batch(order[:4])
        assert np.array_equal(np.asarray(ref), np.asarray(x))
        assert pf.store.retries == 2
    finally:
        pf.close()


# ------------------------------------------- cache owner-rank fix ----
def test_owner_rank_redistribution_multidevice(multidevice):
    """Under 2-way data parallelism with a shuffled epoch, samples move
    between ranks across epochs, so cache hits split into local AND
    redistributed bytes (the owner-rank fix: rank 0 no longer claims
    every hyperslab)."""
    multidevice("""
import numpy as np, tempfile
from jax.sharding import PartitionSpec as P
from repro.core import compat
from repro.data import pipeline, store, synthetic

d = tempfile.mkdtemp()
cubes, targets = synthetic.make_cosmology_dataset(8, 16, channels=2, seed=0)
store.write_dataset(d, cubes, targets)
mesh = compat.make_mesh((2, 1), ('data', 'model'))
ld = pipeline.SpatialParallelLoader(
    store.HyperslabStore(d), mesh, P('data', 'model', None, None, None),
    global_batch=4, seed=0)
for _ in range(3):  # shuffled epochs: sample->rank assignment changes
    order = ld.epoch_schedule()
    for lo in range(0, 8, 4):
        ld.load_batch(order[lo:lo + 4])
assert ld.stats.cache_bytes_redistributed > 0, ld.stats
assert ld.stats.cache_bytes_local > 0, ld.stats
assert 0 < ld.stats.cache_hit_ratio() < 1 or ld.stats.pfs_bytes == 0
print('owner-rank ok', ld.stats)
""", devices=2)


def test_single_rank_cache_hits_all_local(tmp_path):
    """On a 1x1 mesh every hit must be local — the rank map has one
    owner, so redistribution stays exactly zero."""
    root = _dataset(str(tmp_path))
    ld = _loader(root, seed=0)
    for _ in range(2):
        order = ld.epoch_schedule()
        for lo in range(0, 8, 4):
            ld.load_batch(order[lo:lo + 4])
    assert ld.stats.cache_bytes_local > 0
    assert ld.stats.cache_bytes_redistributed == 0


# ------------------------------------------------- label cache ----
def test_vector_label_cache(tmp_path):
    root = _dataset(str(tmp_path))
    ld = _loader(root, seed=0)
    order = ld.epoch_schedule()
    ld.load_batch(order[:4])
    n0 = ld.stats.label_fetches
    assert n0 == 4
    ld.load_batch(order[:4])  # repeat batch: served from the label cache
    assert ld.stats.label_fetches == n0
    ld.load_batch(order[4:8])
    assert ld.stats.label_fetches == n0 + 4


def test_sample_parallel_label_cache(tmp_path):
    root = _dataset(str(tmp_path))
    ld = pipeline.SampleParallelLoader(
        store.HyperslabStore(root), _mesh11(), SPEC, global_batch=4, seed=0)
    ids = np.arange(4)
    ld.load_batch(ids)
    n0 = ld.stats.label_fetches
    ld.load_batch(ids)
    assert ld.stats.label_fetches == n0


# ------------------------------------------------- halo margin reads ----
def test_halo_voxels_reads_margin_serves_exact_slab(tmp_path):
    root = _dataset(str(tmp_path))
    plain = _loader(root, cache=False)
    halo = _loader(root, cache=False, halo=2)
    ids = np.arange(4)
    xa, _ = plain.load_batch(ids)
    xb, _ = halo.load_batch(ids)
    # served content is hyperslab-exact, margin or not
    assert np.array_equal(np.asarray(xa), np.asarray(xb))
    # ...but the halo loader READ more bytes (the margin)
    assert halo.stats.pfs_bytes >= plain.stats.pfs_bytes
    # on a sliced dim the margin strictly widens the read; on the 1x1
    # mesh the whole volume is one slab, so clamping makes them equal
    dims = plain.store.sample_shape[:3]
    wide = halo._expand((slice(4, 8), slice(0, 16), slice(0, 16)), dims)
    assert (wide[0].start, wide[0].stop) == (2, 10)
    assert (wide[1].start, wide[1].stop) == (0, 16)  # clamped


# -------------------------------------------- session + supervisor ----
def _smoke_config(**kw):
    from repro import configs
    from repro.api import RunConfig
    cfg = dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                              input_width=16)
    return RunConfig(model=cfg, global_batch=2, total_steps=20, **kw)


def test_session_loader_prefetch_default_and_telemetry(tmp_path):
    from repro.api import compile as api_compile
    root = _dataset(str(tmp_path), n=4, w=16)
    sess = api_compile(_smoke_config(data_dir=root))
    try:
        ld = sess.make_loader()
        assert isinstance(ld, prefetch.PrefetchLoader)  # config default 2
        order = ld.epoch_schedule()
        x, y = ld.load_batch(order[:2])
        assert np.isfinite(float(sess.step(x, y)))
        tele = sess.telemetry()
        assert tele["io_pfs_bytes"] > 0
        assert "io_stall_s" in tele and "io_queue_occupancy" in tele
        assert 0.0 <= tele["io_cache_hit_ratio"] <= 1.0
        # sync loaders keep the API but skip the queue keys
        sess2 = api_compile(_smoke_config(data_dir=root, prefetch=0))
        ld2 = sess2.make_loader()
        assert isinstance(ld2, pipeline.SpatialParallelLoader)
        ld2.load_batch(ld2.epoch_schedule()[:2])
        t2 = sess2.telemetry()
        assert "io_queue_occupancy" not in t2 and t2["io_pfs_bytes"] > 0
        sess2.close()
    finally:
        sess.close()


def test_runconfig_prefetch_validation_and_roundtrip():
    from repro.api import RunConfig
    from repro.api.config import RunConfigError
    with pytest.raises(RunConfigError, match="prefetch"):
        _smoke_config(prefetch=-1).validate(device_count=1)
    cfg = _smoke_config(prefetch=3)
    assert RunConfig.from_json(cfg.to_json()).prefetch == 3
    # old checkpoints (no prefetch key) get the default
    d = cfg.to_json()
    del d["prefetch"]
    assert RunConfig.from_json(d).prefetch == 2


def test_supervisor_loader_mode_kill_resume_bitwise(tmp_path):
    """With config.data_dir set the supervisor streams real store data
    through the prefetching loader; a kill-and-resume run must replay
    the exact batch sequence — losses bitwise vs uninterrupted, and vs
    the sync (prefetch=0) oracle."""
    from repro.api import supervisor
    root = _dataset(str(tmp_path / "data"), n=4, w=16)

    def run(ckpt, prefetch, fault=None):
        cfgr = _smoke_config(data_dir=root, prefetch=prefetch,
                             checkpoint_dir=str(tmp_path / ckpt))
        if fault is None:
            r = supervisor.run(cfgr, 6, save_every=2)
        else:
            with faults.active(fault):
                r = supervisor.run(cfgr, 6, save_every=2)
        r.session.close()
        return r

    ref = run("ck_ref", 2)
    sync = run("ck_sync", 0)
    assert ref.losses == sync.losses  # prefetch == sync oracle
    kill = run("ck_kill", 2,
               faults.FaultSpec("device.loss", at_steps=(4,), max_fires=1))
    assert kill.restarts == 1 and kill.resumes == 1
    assert kill.losses == ref.losses  # bitwise across kill-and-resume


# ------------------------------------------------------- loader spans ----
def _span_tree(events):
    """Each ``io.load`` span with the spans of its thread inside it."""
    loads = [e for e in events if e.name == "io.load"]
    return [(e, [c for c in events if c is not e and c.thread == e.thread
                 and e.ts_ns <= c.ts_ns
                 and c.ts_ns + c.dur_ns <= e.ts_ns + e.dur_ns])
            for e in loads]


@pytest.mark.parametrize("mode", ["prefetch", "sync", "fallback"])
def test_loader_spans_one_load_per_batch(tmp_path, mode):
    """One ``io.load`` per batch, on the thread that loaded it: a prefetch
    worker, or the caller (a sync loader, or a prefetcher's fallback on
    unpredicted ids). Inside it, one ``io.read`` per store read, whose
    bytes add up to the batch's, and one ``io.place``."""
    from repro.obs import trace as trace_lib

    root = _dataset(str(tmp_path))
    ld = _loader(root, seed=2, cache=False, pf=0 if mode == "sync" else 2)
    tracer = trace_lib.enable()
    try:
        order = ld.schedule_for_epoch(0)
        # the fallback's second batch is not the next chunk of the order
        second = order[2:6] if mode == "fallback" else order[4:8]
        out = [ld.load_batch(b) for b in (order[:4], second)]
        ld.close()
    finally:
        trace_lib.disable(tracer)
    events = [e for e in tracer.events() if e.dur_ns is not None]
    assert not [e for e in events if e.name == "io.load.sync"]
    tree = _span_tree(events)
    caller = threading.current_thread().name
    threads = [e.thread for e, _ in tree]
    if mode == "sync":
        assert threads == [caller, caller]
    elif mode == "prefetch":
        assert len(tree) == 2
        assert all(t.startswith("io-prefetch") for t in threads)
    else:  # the unpredicted batch loads on the caller's thread
        assert threads[0].startswith("io-prefetch")
        assert caller in threads
    x_bytes = out[0][0].nbytes
    for load, inner in tree:
        assert load.attrs == {"samples": 4}
        reads = [c for c in inner if c.name == "io.read"]
        places = [c for c in inner if c.name == "io.place"]
        assert len(reads) == 4
        assert sum(c.attrs["bytes"] for c in reads) == x_bytes
        assert len(places) == 1
        assert places[0].attrs["bytes"] == x_bytes + out[0][1].nbytes
    assert len(events) == sum(1 + len(inner) for _, inner in tree) + (
        mode != "sync") * sum(e.name == "io.wait" for e in events)


def test_loader_shards_and_bytes_across_devices(multidevice):
    """Shards are read before they are placed, one per device as
    ``make_array_from_callback`` asks: a sharded batch and its voxel
    labels equal the store's contents, and a layout replicated over
    ``model`` reads each replica's shard once per device, as a
    per-device callback does."""
    multidevice("""
import numpy as np, tempfile
from jax.sharding import PartitionSpec as P
from repro.core import compat
from repro.data import pipeline, store

rng = np.random.default_rng(0)
cubes = [rng.standard_normal((8, 8, 8, 2), dtype=np.float32)
         for _ in range(4)]
labels = [rng.integers(0, 3, (8, 8, 8)).astype(np.int32) for _ in range(4)]
d = tempfile.mkdtemp()
store.write_dataset(d, cubes, labels=labels)
mesh = compat.make_mesh((2, 2), ('data', 'model'))
ids = np.array([2, 0, 3, 1])
want_x = np.stack([cubes[i] for i in ids])
want_y = np.stack([labels[i] for i in ids])
ld = pipeline.SpatialParallelLoader(
    store.HyperslabStore(d), mesh, P('data', 'model', None, None, None),
    global_batch=4, cache=False, label_spec=P('data', 'model', None, None))
x, y = ld.load_batch(ids)
assert np.array_equal(np.asarray(x), want_x)
assert np.array_equal(np.asarray(y), want_y)
assert x.sharding == ld.sharding and y.sharding == ld.label_sharding
assert ld.stats.pfs_bytes == want_x.nbytes + want_y.nbytes, ld.stats
rep = pipeline.SpatialParallelLoader(
    store.HyperslabStore(d), mesh, P('data', None, None, None, None),
    global_batch=4, cache=False, label_spec=P('data', None, None, None))
x, y = rep.load_batch(ids)
assert np.array_equal(np.asarray(x), want_x)
assert np.array_equal(np.asarray(y), want_y)
assert rep.stats.pfs_bytes == 2 * (want_x.nbytes + want_y.nbytes)
print('shards ok')
""", devices=4)


# ------------------------------------------------- in-place batches ----
def _stacked(root, ids, what="x"):
    """The plain reference: each sample's whole file, stacked."""
    return np.stack([np.load(f"{root}/{what}_{int(i):06d}.npy")
                     for i in ids])


_IN_PLACE_CASES = {  # cache, halo margin, voxel labels, in-place share
    "whole": (False, 0, False, 1.0),
    "halo": (False, 1, False, 0.0),
    "cache": (True, 0, False, 0.0),
    "voxel": (False, 0, True, 1.0),
}


@pytest.mark.parametrize("case", list(_IN_PLACE_CASES))
def test_in_place_batches_bitwise_equal_stacked_loads(tmp_path, case):
    """Two shuffled epochs of batches read into the loader's reused
    buffers equal ``np.stack`` of plain ``np.load`` reads, bit for bit:
    straight from the store, through the halo margin's widened read, from
    the cache (epoch 1 reads nothing), and with voxel labels."""
    cache, halo, voxel, share = _IN_PLACE_CASES[case]
    root = str(tmp_path)
    label_spec = None
    if voxel:
        cubes, labels = synthetic.make_segmentation_dataset(
            8, 16, channels=2, seed=0)
        store.write_dataset(root, cubes, labels=labels)
        label_spec = P("data", "model", None, None)
    else:
        _dataset(root)
    ld = pipeline.SpatialParallelLoader(
        store.HyperslabStore(root), _mesh11(), SPEC, global_batch=4,
        seed=3, cache=cache, label_spec=label_spec, halo_voxels=halo)
    for epoch in range(2):
        before = ld.stats.pfs_bytes
        order = ld.epoch_schedule()
        for lo in range(0, 8, 4):
            ids = order[lo:lo + 4]
            x, y = ld.load_batch(ids)
            assert np.array_equal(np.asarray(x), _stacked(root, ids))
            if voxel:
                assert np.array_equal(np.asarray(y), _stacked(root, ids, "y"))
        if cache and epoch == 1:
            assert ld.stats.pfs_bytes == before  # all from the cache
    assert ld.stats.bytes_in_place == share * ld.stats.pfs_bytes


_SHARDED_SCRIPT = """
import numpy as np, tempfile
from jax.sharding import PartitionSpec as P
from repro.core import compat
from repro.data import pipeline, store, synthetic

d = tempfile.mkdtemp()
cubes, targets = synthetic.make_cosmology_dataset(8, 16, channels=2, seed=0)
store.write_dataset(d, cubes, targets)
mesh = compat.make_mesh((1, {n}), ('data', 'model'))
ld = pipeline.SpatialParallelLoader(
    store.HyperslabStore(d), mesh, P({spec}), global_batch=4, seed=1,
    cache=False)
for _ in range(2):
    order = ld.epoch_schedule()
    for lo in range(0, 8, 4):
        ids = order[lo:lo + 4]
        x, _ = ld.load_batch(ids)
        assert x.sharding == ld.sharding
        assert np.array_equal(np.asarray(x),
                              np.stack([cubes[i] for i in ids]))
assert ld.stats.bytes_in_place == ld.stats.pfs_bytes > 0, ld.stats
print('in place ok')
"""


@pytest.mark.parametrize("n,spec", [
    (2, "'data', 'model', None, None, None"),   # depth: one byte range
    (4, "'data', 'model', None, None, None"),
    (2, "'data', None, 'model', None, None"),   # H: the copy fallback
], ids=["depth2", "depth4", "height2"])
def test_in_place_sharded_batches_bitwise_equal(multidevice, n, spec):
    """Depth-sharded shards (each one ``readinto``) and H-sliced shards
    (copied out of a memory map) land in their buffers bit for bit."""
    assert "in place ok" in multidevice(
        _SHARDED_SCRIPT.format(n=n, spec=spec), devices=n)


@pytest.mark.parametrize("pf", [0, 2], ids=["sync", "prefetch"])
def test_held_batch_unchanged_by_later_loads(tmp_path, pf):
    """The buffer-ownership rule: a batch the caller still holds is not
    overwritten when the loader reuses host buffers for the next two."""
    root = _dataset(str(tmp_path))
    ld = _loader(root, seed=4, cache=False, pf=pf)
    try:
        order = ld.schedule_for_epoch(0)
        held = [ld.load_batch(order[lo:lo + 4]) for lo in (0, 4)]
        ld.load_batch(order[:4])
        ld.load_batch(order[4:8])
        for lo, (x, _) in zip((0, 4), held):
            assert np.array_equal(np.asarray(x),
                                  _stacked(root, order[lo:lo + 4]))
    finally:
        ld.close()


class _Placed:
    """Stands in for a placed array whose transfer may still run."""

    def __init__(self):
        self.ready = self.deleted = False
        self.addressable_shards = []

    def is_ready(self):
        return self.ready

    def is_deleted(self):
        return self.deleted


def test_batch_buffers_lend_only_ready_unaliased():
    """A buffer is lent again only once the array placed from it reports
    ready, and never when the array uses its memory (a CPU placement
    without a copy) or was deleted."""
    pool = pipeline.BatchBuffers()
    a = pool.take((2, 3), np.float32)
    placed = _Placed()
    pool.give([a], placed)
    b = pool.take((2, 3), np.float32)
    assert b is not a  # a's transfer has not ended: a new buffer
    assert pool.take((3, 2), np.float32) is not a  # other shapes never
    placed.ready = True
    assert pool.take((2, 3), np.float32) is a
    gone = _Placed()
    gone.ready = gone.deleted = True
    pool.give([b], gone)
    assert pool.take((2, 3), np.float32) is not b
    # a 4 KiB-aligned host array is placed on the CPU without a copy
    raw = np.empty(4096 + 2 * 4096, np.uint8)
    off = -raw.ctypes.data % 4096
    c = raw[off:off + 4096].view(np.float32).reshape(8, 128)
    x = jax.block_until_ready(jax.device_put(c))
    assert x.addressable_shards[0].data.unsafe_buffer_pointer() \
        == c.ctypes.data
    pool.give([c], x)
    assert pool.take(c.shape, c.dtype) is not c  # c now belongs to x


@pytest.mark.parametrize("cache,halo,share", [
    (False, 0, 1.0), (True, 0, 0.0), (False, 1, 0.0)],
    ids=["streaming", "cached", "halo"])
def test_session_io_in_place_share(tmp_path, cache, halo, share):
    """``io_in_place_share``: 1.0 when every store read lands in a batch
    buffer (cache off, no margin); 0 through the cache, over a cached
    epoch too, and with a halo margin."""
    from repro.api import compile as api_compile
    root = _dataset(str(tmp_path), n=4, w=16)
    sess = api_compile(_smoke_config(data_dir=root))
    try:
        ld = sess.make_loader(cache=cache, prefetch=0, halo_voxels=halo)
        for _ in range(2):
            order = ld.epoch_schedule()
            for lo in range(0, 4, 2):
                ld.load_batch(order[lo:lo + 2])
        tele = sess.telemetry()
        assert tele["io_in_place_share"] == share
        assert tele["io_pfs_bytes"] > 0
    finally:
        sess.close()


# --------------------------------------------------------- store reads ----
_SLABS = {
    "full": (slice(None),) * 4,
    "depth": (slice(4, 12), slice(None), slice(None), slice(None)),
    "height": (slice(None), slice(3, 9), slice(None), slice(None)),
}


@pytest.mark.parametrize("name", list(_SLABS))
def test_read_hyperslab_and_into_agree(tmp_path, name):
    """``read_hyperslab`` (a fresh array) and ``read_hyperslab_into`` (a
    caller's array, here a row of a batch buffer holding stale bytes)
    read the same fragment, one byte range or through the copy."""
    root = _dataset(str(tmp_path), n=2)
    s = store.HyperslabStore(root)
    slab = _SLABS[name]
    want = np.load(f"{root}/x_000001.npy")[slab]
    fresh = s.read_hyperslab(1, slab)
    buf = np.full((2,) + want.shape, np.nan, np.float32)
    row = buf[1]
    assert s.read_hyperslab_into(1, slab, row) is row
    assert np.array_equal(fresh, want) and np.array_equal(buf[1], want)
    assert np.isnan(buf[0]).all()  # only its own row was written
    assert s.bytes_read == 2 * want.nbytes and s.reads == 2
    with pytest.raises(ValueError, match="destination"):
        s.read_hyperslab_into(1, slab, np.empty(want.shape, np.float64))


def test_in_place_transient_fault_retried_identical_batch(tmp_path):
    """A ``loader.read`` transient fired on the in-place path is retried
    by the store and gives the identical batch."""
    root = _dataset(str(tmp_path))
    ids = np.array([5, 1, 6, 2])
    clean, _ = _loader(root, cache=False).load_batch(ids)
    ld = _loader(root, cache=False)
    with faults.active(faults.FaultSpec("loader.read", at_calls=(1, 2),
                                        max_fires=2)):
        x, _ = ld.load_batch(ids)
    assert ld.store.retries == 2
    assert ld.stats.bytes_in_place == ld.stats.pfs_bytes
    assert np.array_equal(np.asarray(x), np.asarray(clean))


def test_truncated_npy_retried_then_healed(tmp_path, monkeypatch):
    """A file shorter than its header says fails the attempt with an
    ``OSError``; the retry after the backoff reads the whole slab."""
    root = _dataset(str(tmp_path))
    path = f"{root}/x_000003.npy"
    whole = open(path, "rb").read()
    with open(path, "r+b") as f:
        f.truncate(len(whole) // 2)

    def heal(_):
        with open(path, "wb") as f:
            f.write(whole)

    monkeypatch.setattr(store.time, "sleep", heal)
    ld = _loader(root, cache=False)
    x, _ = ld.load_batch(np.array([3, 0, 1, 2]))
    assert ld.store.retries == 1
    assert np.array_equal(np.asarray(x), _stacked(root, [3, 0, 1, 2]))


@pytest.mark.parametrize("name", list(_SLABS))
def test_truncated_npy_raises_store_read_error(tmp_path, monkeypatch, name):
    """A truncated ``.npy`` raises ``StoreReadError`` naming the file after
    the capped attempts, into a caller's array or a fresh one."""
    root = _dataset(str(tmp_path), n=2)
    path = f"{root}/x_000001.npy"
    with open(path, "r+b") as f:
        f.truncate(200)
    monkeypatch.setattr(store.time, "sleep", lambda _: None)
    s = store.HyperslabStore(root)
    out = np.empty(np.load(f"{root}/x_000000.npy")[_SLABS[name]].shape,
                   np.float32)
    for read in (lambda: s.read_hyperslab_into(1, _SLABS[name], out),
                 lambda: s.read_hyperslab(1, _SLABS[name])):
        with pytest.raises(StoreReadError, match="x_000001.npy") as ei:
            read()
        assert ei.value.attempts == store.MAX_READ_ATTEMPTS
    assert s.retries == 2 * (store.MAX_READ_ATTEMPTS - 1)
    assert s.reads == 0
