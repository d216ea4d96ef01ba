"""jit wrappers for halo pack/unpack.

Both entry points are live in the runtime halo path (DESIGN.md §3):
``core/halo.py`` calls ``pack`` to extract the two send faces in one fused
pass inside ``start_halo_exchange`` (the overlapped conv), and ``unpack``
to stitch received slabs onto the local block when a conv falls back to
the undecomposed lowering — both under ``use_pallas=True``, threaded from
the models through ``spatial_conv.conv3d``.

As in ``kernels/bn_act/ops.py``, the interpret-mode decision is made at
trace time, and each kernel carries a ``custom_vjp`` whose backward is
the jnp oracle's VJP (Pallas calls have no transpose rule of their own).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.halo_pack import ref
from repro.kernels.halo_pack.kernel import pack_depth, unpack_depth


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pack(x, lo, hi):
    return pack_depth(x, lo, hi, interpret=_interpret())


def _pack_fwd(x, lo, hi):
    return _pack(x, lo, hi), x


def _pack_bwd(lo, hi, x, g):
    _, vjp = jax.vjp(lambda x: ref.pack(x, 1, lo, hi), x)
    return vjp(g)


_pack.defvjp(_pack_fwd, _pack_bwd)


@jax.custom_vjp
def _unpack(x, lo_buf, hi_buf):
    return unpack_depth(x, lo_buf, hi_buf, interpret=_interpret())


def _unpack_fwd(x, lo_buf, hi_buf):
    return _unpack(x, lo_buf, hi_buf), (x, lo_buf, hi_buf)


def _unpack_bwd(res, g):
    _, vjp = jax.vjp(lambda x, a, b: ref.unpack(x, a, b, 1), *res)
    return vjp(g)


_unpack.defvjp(_unpack_fwd, _unpack_bwd)


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def pack(x: jax.Array, lo: int, hi: int):
    """(N,D,H,W,C) -> (lo_face = leading ``hi`` rows, sent to the previous
    rank; hi_face = trailing ``lo`` rows, sent to the next rank)."""
    return _pack(x, lo, hi)


@jax.jit
def unpack(x: jax.Array, lo_buf: jax.Array, hi_buf: jax.Array):
    """One fused write of [lo_buf | x | hi_buf] along depth."""
    return _unpack(x, lo_buf, hi_buf)
