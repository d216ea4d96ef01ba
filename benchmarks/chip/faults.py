"""Faults planted under a cell's timed path, each of which a sound
benchmark must read as not ``correct``. The tests plant them at small
sizes; ``control.py`` plants them at a cell's own size on the chip to
read how far each moves the compared numbers."""
from __future__ import annotations

import contextlib


def state_unchanged(session):
    """Every training step returns the parameters and optimizer state it
    was given (its loss is still computed)."""
    import jax
    import jax.numpy as jnp

    step = session._step_fn

    def frozen(p, o, x, y, s):
        out = step(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o),
                   x, y, s)
        return (p, o) + tuple(out[2:])

    session._step_fn = frozen


def half_batch(session):
    """Each step sees only the first half of its batch, twice: the mean is
    taken over the half."""
    import jax.numpy as jnp

    step = session._step_fn

    def half(p, o, x, y, s):
        h = x.shape[0] // 2
        x2 = jnp.concatenate([x[:h], x[:h]])
        y2 = jnp.concatenate([y[:h], y[:h]])
        return step(p, o, x2.reshape(x.shape), y2, s)

    session._step_fn = half


@contextlib.contextmanager
def no_exchange():
    """Every halo permute between chips delivers zeros, while the block
    is open (programs traced in it keep the fault)."""
    import jax
    import jax.numpy as jnp
    from repro.core import halo

    class Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def ppermute(x, *args, **kwargs):
            return jnp.zeros_like(x)

    saved = halo.lax
    halo.lax = Lax()
    try:
        yield
    finally:
        halo.lax = saved
