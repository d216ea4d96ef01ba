"""Process-wide lowering flags, each read by the conv-net path.

* ``remat``: wrap each conv block in ``jax.checkpoint`` (recompute
  activations in backward) — the standard memory/compute trade. Conv
  nets honor it per block whenever their ``ParallelPlan`` sets no
  stage-level ``remat`` of its own (a plan that does set one wins
  outright — DESIGN.md §9).
* ``overlap_halo``: lower distributed convs via the interior/boundary
  decomposition with packed halo exchange (DESIGN.md §3) instead of the
  blocking exchange-concat-conv. On by default; the blocking path remains
  as the equivalence oracle (``conv3d(..., overlap=False)``).
* ``grad_comm``: gradient-reduction lowering for the conv-net train step
  (DESIGN.md §4): ``"overlap"`` (default — per-layer bucketed reduction
  hooks that fire during backward), ``"monolithic"`` (the tail tree-wide
  psum, kept as the equivalence oracle), or ``"reduce_scatter"``
  (ZeRO-1: psum_scatter + sharded optimizer + all_gather).
* ``pipeline_link_latency_s``: emulated one-way latency of the
  inter-group link crossed at pipeline stage boundaries (DESIGN.md
  §13). On the forced-host-device test topology the cross-group
  ``device_put`` is a free memcpy, which flatters any blocking
  schedule; the pipeline bench sets this (like the io bench throttles
  its store) so the measured 1F1B-vs-sequential gap reflects how much
  link latency each schedule hides. ``0.0`` (default) = no emulation.
"""
from __future__ import annotations

import contextlib

_STATE = {"remat": False, "overlap_halo": True, "grad_comm": "overlap",
          "pipeline_link_latency_s": 0.0}


def get(name: str):
    return _STATE[name]


def snapshot() -> dict:
    """Copy of the full flag state (bench provenance, debugging)."""
    return dict(_STATE)


def set_flags(**kw) -> None:
    for k, v in kw.items():
        if k not in _STATE:
            raise KeyError(k)
        _STATE[k] = v


@contextlib.contextmanager
def flags(**kw):
    old = dict(_STATE)
    set_flags(**kw)
    try:
        yield
    finally:
        _STATE.update(old)
