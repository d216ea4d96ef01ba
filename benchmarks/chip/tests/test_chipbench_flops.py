"""The operation and byte counter (``flops.py``) against the paper's
numbers and against XLA's own count of a plain conv stack."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402
from benchmarks.chip import flops, reference  # noqa: E402


def _model(name):
    with open(os.path.join(small.CHIP, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def _cosmoflow_256():
    """CosmoFlow at 256^3 x 4: the same widths, one more pool."""
    return dict(_model("cosmoflow-128"), input_width=256)


def test_cosmoflow_128_forward_is_18_53_gflop():
    m = _model("cosmoflow-128")
    assert flops.forward_flops(m) / 1e9 == pytest.approx(18.525, abs=5e-4)
    # forward and backward, without block 0's input gradient
    assert flops.train_flops(m) / 1e9 == pytest.approx(48.33, abs=5e-3)


def test_cosmoflow_256_counts_each_layer_once():
    m = _cosmoflow_256()
    assert flops.train_flops(m) / 1e9 == pytest.approx(385.83, abs=5e-3)


def test_layer_shapes_match_the_reference_parameters():
    for m in (_model("cosmoflow-128"), _cosmoflow_256()):
        shapes = reference.param_shapes(m)
        for c in flops.conv_layers(m):
            assert shapes[f"conv{c['block']}_w"] == (
                3, 3, 3, c["c_in"], c["c_out"])
        for j, (a, b) in enumerate(flops.fc_layers(m)):
            assert shapes[f"fc{j}_w"] == (a, b)
        assert sum(math_prod(s) for s in shapes.values()) == m["params"]


def math_prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _xla_cost(fn, *args):
    import jax

    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


def _conv_stack(m):
    def run(params, x):
        h = x
        for c in flops.conv_layers(m):
            h = reference._conv(h, params[f"conv{c['block']}_w"], c["stride"])
            if c["pool"]:
                h = reference._maxpool(h)
        return h

    return run


@pytest.mark.parametrize("model", [small.SMALL_MODEL, "cosmoflow-smoke"])
def test_agrees_with_xla_cost_analysis(model):
    """XLA counts only the taps that fall inside the volume; the model
    count takes every tap, as FLOP counts of convs are quoted. With SAME
    padding XLA's count is therefore lower, by under 15% at these small
    widths (it shrinks with the width); the two must agree to 1% on
    taps that all fall inside."""
    import jax
    import jax.numpy as jnp

    if model == "cosmoflow-smoke":
        model = small.smoke_model()
    params = jax.eval_shape(lambda k: reference.init_params(k, model),
                            jax.random.PRNGKey(0))
    w = model["input_width"]
    x = jax.ShapeDtypeStruct((1, w, w, w, model["in_channels"]), jnp.float32)
    ours = flops.conv_flops(model, train=False)
    same = _xla_cost(_conv_stack(model), params, x)["flops"]
    assert 0.85 * ours <= same <= ours
    # where every tap falls inside (a VALID conv), the counts agree
    for c in flops.conv_layers(model):
        s = c["stride"]
        if c["w_in"] < 3:
            continue
        xi = jax.ShapeDtypeStruct((1,) + (c["w_in"],) * 3 + (c["c_in"],),
                                  jnp.float32)
        wi = jax.ShapeDtypeStruct((3, 3, 3, c["c_in"], c["c_out"]),
                                  jnp.float32)
        n_out = (c["w_in"] - 3) // s + 1
        xla = _xla_cost(lambda a, b, s=s: jax.lax.conv_general_dilated(
            a, b, (s,) * 3, "VALID",
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC")), xi, wi)["flops"]
        layer = dict(c, w_out=n_out)
        assert xla == pytest.approx(flops._conv_fwd_flops(layer), rel=0.01)
