"""Plain CosmoFlow (Oyama et al. 2020, Table I and section IV) in
``jax.numpy``, float32 at "highest" precision: the yardstick a cell's
training steps are compared against.

It imports nothing of the system under test. It follows the published
description:

- ``n`` conv blocks of 3x3x3 SAME convs without bias (stride 2 in block
  4, the fourth), each followed by batch-norm with batch statistics over
  (N, D, H, W) (eps 1e-5), leaky-ReLU (slope 0.01), and a 2x2x2 max-pool
  for the first ``log2(W) - 2`` blocks;
- a fully connected head 2048 -> 256 -> out with leaky-ReLU and dropout
  (keep 0.8) after each hidden layer, then the mean squared error;
- Adam (beta1 0.9, beta2 0.999, eps 1e-8) with the learning rate decayed
  linearly to 1% of its start over ``total_steps``.

Two choices are this benchmark's, not the paper's, and the program under
test must make the same ones: the weights are He-normal draws made here
from the seed (``init_params``), and the dropout mask of sample ``i`` in
hidden layer ``j`` at step ``t`` is ``bernoulli(fold_in(fold_in(
PRNGKey(t), j), i), 0.8)``.

At 256^3 one sample does not fit one chip: ``act_sharding`` shards the
activations of the wide blocks on depth over a mesh and lets the compiler
place the collectives.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
KEEP = 0.8
SLOPE = 0.01
BN_EPS = 1e-5


def layer_widths(width: int, n_blocks: int):
    """Input depth of each conv block, and the width after the last."""
    n_pool = min(int(math.log2(width)) - 2, n_blocks)
    ins, w = [], width
    for i in range(n_blocks):
        ins.append(w)
        if i == 3:
            w //= 2
        if i < n_pool:
            w //= 2
    return ins, w


def param_shapes(m: dict) -> Dict[str, tuple]:
    """Leaf name -> shape for the model description ``m`` (the ``model``
    group of a configuration file)."""
    chans = list(m["conv_channels"])
    _, w_out = layer_widths(m["input_width"], len(chans))
    out, cin = {}, m["in_channels"]
    for i, c in enumerate(chans):
        out[f"conv{i}_w"] = (3, 3, 3, cin, c)
        out[f"bn{i}_scale"] = (c,)
        out[f"bn{i}_bias"] = (c,)
        cin = c
    flat = cin * w_out ** 3
    for j, d in enumerate(list(m["fc_dims"]) + [m["out_dim"]]):
        out[f"fc{j}_w"] = (flat, d)
        out[f"fc{j}_b"] = (d,)
        flat = d
    return out


def init_params(key, m: dict) -> Dict[str, jax.Array]:
    """He-normal conv and FC weights, unit BN scales, zero biases."""
    shapes = param_shapes(m)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        if name.startswith("bn") and name.endswith("scale"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_b") or name.endswith("bias"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            fan_in = math.prod(shape[:-1])
            out[name] = (jax.random.normal(keys[name], shape, jnp.float32)
                         * math.sqrt(2.0 / fan_in))
    return out


def _conv(h, w, stride):
    # SAME padding as XLA defines it: total k - s, the smaller half low
    total = max(3 - stride, 0)
    pad = (total // 2, total - total // 2)
    return lax.conv_general_dilated(
        h, w, (stride,) * 3, [pad] * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"), precision=HIGHEST)


def _batchnorm_lrelu(h, scale, bias):
    axes = tuple(range(h.ndim - 1))
    mean = jnp.mean(h, axis=axes)
    var = jnp.mean(jnp.square(h - mean), axis=axes)
    y = (h - mean) * lax.rsqrt(var + BN_EPS) * scale + bias
    return jnp.where(y >= 0, y, SLOPE * y)


def _maxpool(h):
    return lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 2, 1),
                             (1, 2, 2, 2, 1), "VALID")


def forward(params, x, m: dict, *, dropout_step=None,
            act_sharding: Optional[Callable] = None):
    """(N, W, W, W, C) -> (N, out). ``dropout_step`` (an int) turns on
    training dropout with that step's masks; ``act_sharding(h)`` may
    constrain each block's output layout (it must not change values)."""
    n = len(m["conv_channels"])
    n_pool = min(int(math.log2(m["input_width"])) - 2, n)
    h = x
    for i in range(n):
        h = _conv(h, params[f"conv{i}_w"], 2 if i == 3 else 1)
        h = _batchnorm_lrelu(h, params[f"bn{i}_scale"], params[f"bn{i}_bias"])
        if i < n_pool:
            h = _maxpool(h)
        if act_sharding is not None:
            h = act_sharding(h)
    h = h.reshape(h.shape[0], -1)
    n_fc = len(m["fc_dims"]) + 1
    for j in range(n_fc):
        h = jnp.dot(h, params[f"fc{j}_w"], precision=HIGHEST) + params[f"fc{j}_b"]
        if j < n_fc - 1:
            h = jnp.where(h >= 0, h, SLOPE * h)
            if dropout_step is not None:
                layer = jax.random.fold_in(jax.random.PRNGKey(dropout_step), j)
                mask = jax.vmap(lambda i: jax.random.bernoulli(
                    jax.random.fold_in(layer, i), KEEP, (h.shape[1],)))(
                        jnp.arange(h.shape[0]))
                h = jnp.where(mask, h / KEEP, 0.0)
    return h


def mse(params, x, y, m: dict, step: int, act_sharding=None):
    pred = forward(params, x, m, dropout_step=step, act_sharding=act_sharding)
    return jnp.mean(jnp.mean(jnp.square(pred - y), axis=-1))


def learning_rate(opt: dict, t):
    """Linear decay to 1% of ``lr`` over ``total_steps`` (t counts from 1)."""
    frac = jnp.clip(t / opt["total_steps"], 0.0, 1.0)
    return opt["lr"] * (1.0 - 0.99 * frac)


def adam(params, grads, m_state, v_state, t, opt: dict):
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = jnp.asarray(t, jnp.float32)
    lr = learning_rate(opt, t)
    m_new, v_new, p_new = {}, {}, {}
    for k in params:
        m_new[k] = b1 * m_state[k] + (1 - b1) * grads[k]
        v_new[k] = b2 * v_state[k] + (1 - b2) * jnp.square(grads[k])
        mh = m_new[k] / (1 - b1 ** t)
        vh = v_new[k] / (1 - b2 ** t)
        p_new[k] = params[k] - lr * mh / (jnp.sqrt(vh) + eps)
    return p_new, m_new, v_new


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_step(m: dict, opt: dict, act_sharding=None):
    """One training step: (params, Adam moments, x, y, step index from 0)
    -> (params, moments, loss, each leaf's gradient norm)."""

    def step(p, ms, vs, x, y, t):
        loss, g = jax.value_and_grad(mse)(p, x, y, m, t, act_sharding)
        p2, ms2, vs2 = adam(p, g, ms, vs, t + 1, opt)
        return p2, ms2, vs2, loss, leaf_norms(g)

    return step


def train_readings(params0, batches: Sequence, m: dict, opt: dict,
                   act_sharding=None) -> dict:
    """Run ``len(batches)`` reference steps from ``params0``: each step's
    loss, each leaf's norm of the first gradient, and each leaf's norm of
    the change of the parameters over all the steps. ``batches`` holds
    ``(x, y)`` pairs; step ``t`` (from 0) uses dropout masks of step t."""
    step = jax.jit(make_step(m, opt, act_sharding))
    zeros = {k: jnp.zeros_like(v) for k, v in params0.items()}
    p, ms, vs = params0, zeros, dict(zeros)
    losses, grad_norms = [], None
    for t, (x, y) in enumerate(batches):
        p, ms, vs, loss, gn = step(p, ms, vs, x, y, jnp.int32(t))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in gn.items()}
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(p, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(v) for k, v in change.items()}}
