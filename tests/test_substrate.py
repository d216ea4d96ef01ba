"""Substrate tests: data store/pipeline (spatial-parallel I/O semantics),
optimizer convergence, checkpoint roundtrip, configs registry, serving."""
import os

import jax
import jax.numpy as jnp

from repro.core import compat
import numpy as np
import pytest

from repro import configs


def test_store_hyperslab_reads(tmp_path):
    from repro.data import store, synthetic
    cubes, targets = synthetic.make_cosmology_dataset(3, 16, channels=2,
                                                      seed=0)
    store.write_dataset(str(tmp_path), cubes, targets)
    s = store.HyperslabStore(str(tmp_path))
    assert s.num_samples == 3 and s.sample_shape == (16, 16, 16, 2)
    slab = s.read_hyperslab(1, (slice(4, 8), slice(0, 16), slice(0, 16),
                                slice(None)))
    np.testing.assert_allclose(slab, cubes[1][4:8])
    # hyperslab read touches only the slab bytes
    assert s.bytes_read == slab.nbytes


def test_spatial_parallel_loader_cache_and_counters(tmp_path):
    from repro.data import pipeline, store, synthetic
    from jax.sharding import PartitionSpec as P
    cubes, targets = synthetic.make_cosmology_dataset(4, 8, seed=1)
    store.write_dataset(str(tmp_path), cubes, targets)
    s = store.HyperslabStore(str(tmp_path))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    loader = pipeline.SpatialParallelLoader(
        s, mesh, P("data", "model", None, None, None), global_batch=2,
        seed=0)
    order = loader.epoch_schedule()
    x, y = loader.load_batch(order[:2])
    assert x.shape == (2, 8, 8, 8, 1) and y.shape == (2, 4)
    np.testing.assert_allclose(np.asarray(x)[0], cubes[order[0]], rtol=1e-6)
    epoch0_pfs = loader.stats.pfs_bytes
    assert epoch0_pfs > 0
    # epoch 1: served entirely from the distributed cache
    loader.stats.reset()
    x2, _ = loader.load_batch(order[:2])
    assert loader.stats.pfs_bytes == 0
    assert loader.stats.cache_bytes_local \
        + loader.stats.cache_bytes_redistributed > 0
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x))


def test_sample_parallel_baseline_reads_more(tmp_path):
    """Fig. 5 premise: sample-parallel I/O does not shrink per-rank reads."""
    from repro.data import pipeline, store, synthetic
    from jax.sharding import PartitionSpec as P
    cubes, targets = synthetic.make_cosmology_dataset(2, 8, seed=2)
    store.write_dataset(str(tmp_path), cubes, targets)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    sp = pipeline.SampleParallelLoader(
        store.HyperslabStore(str(tmp_path)), mesh,
        P("data", "model", None, None, None), global_batch=2, seed=0)
    sp.load_batch(np.array([0, 1]))
    # the baseline additionally pays a full redistribution of every sample
    assert sp.stats.cache_bytes_redistributed >= sp.stats.pfs_bytes


def test_adam_converges_on_quadratic():
    from repro.optim.adam import Adam, linear_decay
    opt = Adam(lr=linear_decay(0.1, 200))
    target = jnp.asarray([1.0, -2.0, 3.0])
    p = {"w": jnp.zeros(3)}
    state = opt.init(p)

    @jax.jit
    def step(p, state):
        loss, g = jax.value_and_grad(
            lambda p: jnp.sum((p["w"] - target) ** 2))(p)
        p, state = opt.update(g, state, p)
        return p, state, loss

    for _ in range(200):
        p, state, loss = step(p, state)
    assert float(loss) < 1e-3


def test_checkpoint_roundtrip(tmp_path):
    from repro.train import checkpoint
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)}}
    checkpoint.save(str(tmp_path), tree, step=7)
    restored = checkpoint.restore(str(tmp_path), tree)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", configs.PAPER_ARCHS)
def test_config_registry_integrity(arch):
    cfg = configs.get_config(arch)
    smoke = configs.get_smoke_config(arch)
    assert cfg.name == arch
    assert cfg.family == smoke.family == "conv3d"
    assert cfg.arch == smoke.arch
    assert cfg.param_count() > 0
    assert smoke.param_count() < cfg.param_count()


@pytest.mark.parametrize("width", [128, 256, 512])
def test_published_param_counts(width):
    """CosmoFlow: paper Table I says 9.44M for every input size."""
    n = configs.get_config(f"cosmoflow-{width}").param_count()
    assert abs(n - 9.44e6) < 0.05e6


def test_cosmoflow_memory_matches_table1():
    """Paper Table I memory column: 0.824 / 6.59 / 52.7 GiB per sample."""
    from repro.core.perf_model import memory_per_sample_bytes
    for w, gib in ((128, 0.824), (256, 6.59), (512, 52.7)):
        cfg = configs.get_config(f"cosmoflow-{w}")
        got = memory_per_sample_bytes(cfg, batchnorm=False) / 2 ** 30
        assert abs(got - gib) / gib < 0.05, (w, got, gib)


def test_cosmoflow_flops_match_table1():
    """Paper Table I: 3550 GF/sample fwd+bwd (1183 fwd) at 512^3;
    55.55/18.52 at 128^3."""
    from repro.core.perf_model import conv_net_flops_per_sample
    for w, total, fwd in ((128, 55.55e9, 18.52e9), (256, 443.8e9, 147.9e9),
                          (512, 3550e9, 1183e9)):
        cfg = configs.get_config(f"cosmoflow-{w}")
        got_f = conv_net_flops_per_sample(cfg, forward_only=True)
        assert abs(got_f - fwd) / fwd < 0.1, (w, got_f, fwd)
        got_t = conv_net_flops_per_sample(cfg)
        assert abs(got_t - total) / total < 0.1, (w, got_t, total)
