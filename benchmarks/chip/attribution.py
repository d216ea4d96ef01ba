"""Owners of a traced stretch's device time and idle time, from the same
``.xplane.pb`` that ``xplane.reduce`` reads, and on the same clock.

- ``scope_s``: device seconds per named scope of the program, the mean
  over devices. Each operation belongs to the innermost of ``SCOPES``
  among the path components of its instruction's ``op_name`` metadata
  (read from the optimized HLO the trace carries), else to
  ``unscoped``. Op durations are clipped to the stretch and summed the
  way ``xplane.reduce`` sums them for its ``device_ops``, so the values
  add up to the stretch's device op time.
- ``idle_under_s``: device-idle seconds per program span, the mean over
  devices. Each instant of an idle gap belongs to the innermost span
  open at that instant on the step thread (the thread that emitted
  ``train.step``), else to ``none``.

Host spans are ``(name, start ns, duration ns[, thread name])`` on the
program's ``perf_counter_ns`` clock, as ``xplane.reduce`` takes them.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import xplane

SCOPES = ("conv", "norm", "pool", "head", "loss", "optimizer", "halo",
          "reshard", "grad_comm")
UNSCOPED = "unscoped"
NONE = "none"
STEP_SPAN = "train.step"

_INST = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transformation wraps a name stack's outer part: transpose(jvp(block0))
_WRAPPED = re.compile(r"^(?:[\w.-]*\()*([^()]*)\)*$")


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on the (first) name stack of an
    ``op_name``, or ``unscoped``."""
    found = UNSCOPED
    for part in op_name.split(";", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            found = m.group(1)
    return found


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of a module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(name.group(1)) if name else UNSCOPED
    return out


def _module_ops(plane):
    """(instruction, program, start ns, end ns) of each operation on the
    plane's ops line."""
    modules = []
    for line in plane.lines:
        if line.name == xplane.MODULES_LINE:
            modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                              e.name) for e in line.events)
    starts = [a for a, _, _ in modules]
    for line in plane.lines:
        if line.name != xplane.OPS_LINE:
            continue
        for e in line.events:
            start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
            j = bisect.bisect_right(starts, start) - 1
            module = modules[j][2] if j >= 0 and start < modules[j][1] else ""
            yield e.name.split(" = ", 1)[0].lstrip("%"), module, start, end


def scope_seconds(planes, scopes: Dict[str, Dict[str, str]], lo: int,
                  hi: int) -> Dict[str, float]:
    """``scopes`` maps a program name to ``instruction_scopes`` of its
    HLO; [lo, hi) is the stretch on the profiler's clock."""
    total: Dict[str, int] = defaultdict(int)
    for plane in planes:
        for name, module, a, b in _module_ops(plane):
            if min(b, hi) > max(a, lo):
                scope = scopes.get(module, {}).get(name, UNSCOPED)
                total[scope] += min(b, hi) - max(a, lo)
    return {s: t / len(planes) * 1e-9 for s, t in sorted(total.items())}


def innermost(spans: Sequence[Tuple[str, int, int]], lo: int,
              hi: int) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut where a span (name, start, end) of one thread opens or
    closes, each piece named by the innermost span open over it (the
    latest opened; the earliest closed among those opened together), or
    ``none``."""
    points = sorted({lo, hi} | {min(max(t, lo), hi)
                                for _, a, b in spans for t in (a, b)})
    out = []
    for a, b in zip(points, points[1:]):
        over = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        out.append((a, b, max(over)[2] if over else NONE))
    return out


def step_thread(host_spans) -> Optional[str]:
    """The thread that emitted ``train.step`` (``None`` where spans carry
    no thread)."""
    for s in host_spans:
        if s[0] == STEP_SPAN:
            return s[3] if len(s) > 3 else None
    return None


def idle_under(busy_by_plane: Sequence[Sequence[xplane.Interval]],
               spans: Sequence[Tuple[str, int, int]], lo: int,
               hi: int) -> Dict[str, float]:
    """Device-idle seconds per innermost step-thread span, the mean over
    the planes; ``spans`` are (name, start, end) of the step thread on
    the profiler's clock, ``busy_by_plane`` each plane's busy union."""
    pieces = innermost(spans, lo, hi)
    total: Dict[str, int] = defaultdict(int)
    for busy in busy_by_plane:
        for ga, gb in xplane.gaps(busy, lo, hi):
            for a, b, name in pieces:
                if min(b, gb) > max(a, ga):
                    total[name] += min(b, gb) - max(a, ga)
    return {n: t / len(busy_by_plane) * 1e-9
            for n, t in sorted(total.items())}


def reduce(path: str, n_devices: int, host_spans, anchor_perf_ns: int,
           window_perf_ns: xplane.Interval, data=None,
           scopes=None) -> dict:
    """``scope_s`` and ``idle_under_s`` of the traced stretch; the
    arguments are ``xplane.reduce``'s, and ``data`` and ``scopes`` (a
    program name -> ``instruction_scopes``) stand in for the file's
    contents in tests."""
    if data is None:
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            raw = f.read()
        data = ProfileData.from_serialized_xspace(raw)
        scopes = {name: instruction_scopes(text)
                  for name, text in xplane.hlo_modules(raw).items()}
    anchor = xplane.host_anchor_ns(data)
    if anchor is None:
        raise ValueError(f"no {xplane.ANCHOR!r} annotation in {path}")
    off = anchor - anchor_perf_ns
    lo, hi = window_perf_ns[0] + off, window_perf_ns[1] + off
    planes = xplane.device_planes(data, n_devices)
    if not planes:
        raise ValueError(f"no device planes in {path}")
    thread = step_thread(host_spans)
    step = [(s[0], s[1] + off, s[1] + off + s[2]) for s in host_spans
            if (s[3] if len(s) > 3 else None) == thread]
    busy = [xplane.union(xplane.clip(
        ((a, b) for _, _, a, b in _module_ops(p)), lo, hi)) for p in planes]
    return {"scope_s": scope_seconds(planes, scopes or {}, lo, hi),
            "idle_under_s": idle_under(busy, step, lo, hi)}


def input_spans(host_spans) -> dict:
    """Totals of the input layer's spans, from host spans that carry
    their thread and attributes as fourth and fifth elements: the
    batches, the seconds and bytes of the ``io.read`` and ``io.place``
    spans inside their ``io.load`` on its thread, and ``io.load``'s self
    time, its length less theirs (the host stacking and the loader's own
    Python). A batch counts where both its ``io.load`` and its one
    ``io.place`` were recorded: a span opened while the tracer was off
    records nothing, so a load cut by either end of the stretch lacks
    one of them."""
    out = {"batches": 0, "self_s": 0.0, "read_s": 0.0, "read_bytes": 0,
           "place_s": 0.0, "place_bytes": 0}
    for name, a, d, thread, _ in host_spans:
        if name != "io.load":
            continue
        inner = [c for c in host_spans
                 if c[0] in ("io.read", "io.place") and c[3] == thread
                 and a <= c[1] and c[1] + c[2] <= a + d]
        if not any(c[0] == "io.place" for c in inner):
            continue
        out["batches"] += 1
        out["self_s"] += (d - sum(c[2] for c in inner)) * 1e-9
        for c in inner:
            kind = c[0][len("io."):]
            out[kind + "_s"] += c[2] * 1e-9
            out[kind + "_bytes"] += (c[4] or {}).get("bytes", 0)
    return out
