"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dep: property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.halo import conv_halo_widths


@given(k=st.integers(1, 7), s=st.integers(1, 4))
def test_halo_widths_cover_same_padding(k, s):
    """lo + hi must equal the SAME-conv total padding (k - s when k >= s)."""
    lo, hi = conv_halo_widths(k, s)
    assert lo + hi == max(k - s, 0)
    assert 0 <= lo <= hi <= lo + 1


@settings(deadline=None, max_examples=20)
@given(
    rows=st.integers(1, 64), c=st.sampled_from([1, 3, 8]),
    seed=st.integers(0, 2 ** 16),
)
def test_bn_act_kernel_property(rows, c, seed):
    from repro.kernels.bn_act import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (rows, c))
    mean = jax.random.normal(ks[1], (c,))
    var = jax.nn.softplus(jax.random.normal(ks[2], (c,)))
    scale = jax.random.normal(ks[3], (c,))
    bias = jax.random.normal(ks[4], (c,))
    got = ops.bn_leaky_relu(x, mean, var, scale, bias)
    want = ref.bn_leaky_relu(x, mean, var, scale, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 2 ** 16), steps=st.integers(1, 5))
def test_adam_zero_grad_fixed_point(seed, steps):
    from repro.optim.adam import Adam, constant
    opt = Adam(lr=constant(1e-2))
    p = {"w": jax.random.normal(jax.random.PRNGKey(seed), (4, 4))}
    state = opt.init(p)
    g = jax.tree.map(jnp.zeros_like, p)
    p2 = p
    for _ in range(steps):
        p2, state = opt.update(g, state, p2)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p["w"]))


@settings(deadline=None, max_examples=10)
@given(w=st.sampled_from([8, 16]), factor=st.sampled_from([2, 4]),
       seed=st.integers(0, 100))
def test_subvolume_split_partitions_exactly(w, factor, seed):
    from repro.data.synthetic import split_into_subvolumes
    rng = np.random.default_rng(seed)
    cube = rng.normal(size=(w, w, w, 1)).astype(np.float32)
    subs, t = split_into_subvolumes([cube], np.zeros((1, 4), np.float32),
                                    factor)
    assert len(subs) == factor ** 3
    total = sum(float(np.sum(s)) for s in subs)
    np.testing.assert_allclose(total, float(np.sum(cube)), rtol=1e-4)
