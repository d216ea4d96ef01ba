"""jit wrapper for fused BN + LeakyReLU.

Two properties the model hot path (``core/dist_norm.py``) relies on:

* the interpret-mode decision is made at TRACE time, not import time — a
  backend selected after import (tests forcing host platforms, AOT
  compiles for a described TPU) must win;
* the kernel carries a ``custom_vjp`` whose backward is the jnp oracle's
  VJP, so the fused forward can sit under ``value_and_grad`` (Pallas
  calls have no transpose rule of their own).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.bn_act.kernel import bn_leaky_relu as _kernel
from repro.kernels.bn_act.ref import bn_leaky_relu as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _bn_act(x, mean, var, scale, bias, eps, negative_slope):
    return _kernel(x, mean, var, scale, bias, eps=eps,
                   negative_slope=negative_slope, interpret=_interpret())


def _bn_act_fwd(x, mean, var, scale, bias, eps, negative_slope):
    return (_bn_act(x, mean, var, scale, bias, eps, negative_slope),
            (x, mean, var, scale, bias))


def _bn_act_bwd(eps, negative_slope, res, g):
    _, vjp = jax.vjp(
        lambda *a: _ref(*a, eps=eps, negative_slope=negative_slope), *res)
    return vjp(g)


_bn_act.defvjp(_bn_act_fwd, _bn_act_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "negative_slope"))
def bn_leaky_relu(x, mean, var, scale, bias, *, eps=1e-5,
                  negative_slope=0.01):
    return _bn_act(x, mean, var, scale, bias, eps, negative_slope)
