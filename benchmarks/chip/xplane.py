"""Reduce a JAX profiler trace (``.xplane.pb``) of a traced stretch to the
per-layer readings and the ``breakdown``.

Per device used: the union of the intervals in which an operation ran
(busy), the device time of operations whose HLO holds a convolution,
and the time in which a collective ran while no other operation did
(exposed collective time). Over all of them: the operations that took
most time, and the longest idle gaps, each labelled by the host span
(the program's own, ``train.step``, ``io.load``, ``serve.forward`` ...)
that overlapped it most.

Which operations hold a convolution is read from the optimized HLO of
each program, which the profiler stores in the trace (``ProfileOptions.
enable_hlo_proto``): an operation is a conv operation where its
instruction is a convolution or calls a computation that holds one.

Host spans come from the program's tracer on ``time.perf_counter_ns``;
an annotation made at a known instant (``ANCHOR``) ties that clock to
the profiler's.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "chipbench.clock_anchor"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|send|recv")

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (sorted, disjoint) intervals ``a`` outside those of
    ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def _wire(buf: bytes):
    """(field number, value) of each field of a protobuf message: ints for
    varints, bytes for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 1:
            val, i = buf[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, val


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def hlo_modules(raw: bytes) -> Dict[str, str]:
    """Program name -> optimized HLO text, from the ``/host:metadata``
    plane of a serialized XSpace (XPlane field 4 maps event metadata,
    whose stats (field 5) carry an HloProto as bytes (field 6), whose
    field 1 is the HloModuleProto)."""
    from jax._src.lib import _jax

    out = {}
    for field, plane in _wire(raw):
        if field != 1 or b"/host:metadata" not in plane[:64]:
            continue
        for f, entry in _wire(plane):
            if f != 4:
                continue
            meta = dict(_wire(entry)).get(2, b"")
            fields = list(_wire(meta))
            name = next((v.decode() for k, v in fields if k == 2), "")
            for k, stat in fields:
                blob = dict(_wire(stat)).get(6) if k == 5 else None
                if blob:
                    module = dict(_wire(blob)).get(1)
                    if module:
                        out[name] = _jax.HloModule.from_serialized_hlo_module_proto(
                            module).to_string()
    return out


_COMP = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?((?:%[\w.-]+(?:, )?)+)")


def conv_instructions(hlo_text: str) -> set:
    """Names of the instructions of a module that are, or call a
    computation holding, a convolution."""
    calls: Dict[str, List[str]] = {}
    direct: Dict[str, bool] = {}
    members: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INST.match(line)
        if not m or comp is None:
            continue
        inst = m.group(1)
        members[comp].append(inst)
        direct[inst] = " convolution(" in line
        calls[inst] = [c.strip().lstrip("%") for g in _CALLS.findall(line)
                       for c in g.split(",")]
    holds: Dict[str, bool] = {}

    def comp_holds(c, seen=()):
        if c not in holds:
            holds[c] = False if c in seen else any(
                inst_holds(i, seen + (c,)) for i in members.get(c, ()))
        return holds[c]

    def inst_holds(i, seen=()):
        return direct.get(i, False) or any(comp_holds(c, seen)
                                           for c in calls.get(i, ()))

    return {i for i in direct if inst_holds(i)}


def device_ops(plane, conv: Dict[str, set]) -> List[Tuple[str, int, int, bool]]:
    """(instruction name, start ns, end ns, holds a convolution) of each
    operation on the plane's ops line; ``conv`` maps a program name to
    its conv instructions."""
    modules = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                              e.name) for e in line.events)
    out = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
            name = e.name.split(" = ", 1)[0].lstrip("%")
            module = next((m for a, b, m in modules if a <= start < b), "")
            out.append((name, start, end, name in conv.get(module, ())))
    return out


def device_planes(data, n_devices: int):
    """The planes of the first ``n_devices`` accelerator devices, by id."""
    planes = []
    for p in data.planes:
        m = re.match(r"/device:(TPU|GPU):(\d+)$", p.name)
        if m:
            planes.append((int(m.group(2)), p))
    planes.sort(key=lambda t: t[0])
    return [p for _, p in planes[:n_devices]]


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def host_anchor_ns(data) -> Optional[int]:
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    return int(e.start_ns)
    return None


def _label(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    best, best_overlap = "none", 0
    for name, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce(path: str, n_devices: int,
           host_spans: Sequence[Tuple[str, int, int]], anchor_perf_ns: int,
           window_perf_ns: Interval, data=None, conv=None) -> dict:
    """``host_spans`` are (name, start, duration) on the perf_counter_ns
    clock, ``anchor_perf_ns`` the instant ``ANCHOR`` was annotated, and
    ``window_perf_ns`` the traced stretch on that clock. ``data`` and
    ``conv`` stand in for the file's contents in tests."""
    if data is None:
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            raw = f.read()
        data = ProfileData.from_serialized_xspace(raw)
        conv = {name: conv_instructions(text)
                for name, text in hlo_modules(raw).items()}
    anchor = host_anchor_ns(data)
    if anchor is None:
        raise ValueError(f"no {ANCHOR!r} annotation in {path}")
    off = anchor - anchor_perf_ns
    lo, hi = window_perf_ns[0] + off, window_perf_ns[1] + off
    spans = [(n, s + off, s + off + d) for n, s, d in host_spans]
    planes = device_planes(data, n_devices)
    if not planes:
        raise ValueError(f"no device planes in {path}")
    busy_ns = conv_ns = exposed_ns = 0
    op_time: Dict[str, int] = defaultdict(int)
    gap_list: List[Tuple[int, str]] = []
    for plane in planes:
        ops = [(n, a, b, c) for n, a, b, c in device_ops(plane, conv or {})
               if min(b, hi) > max(a, lo)]
        busy = union(clip(((a, b) for _, a, b, _ in ops), lo, hi))
        busy_ns += length(busy)
        conv_ns += length(union(clip(
            ((a, b) for _, a, b, c in ops if c), lo, hi)))
        coll = union(clip(((a, b) for n, a, b, _ in ops
                           if is_collective(n)), lo, hi))
        other = union(clip(((a, b) for n, a, b, _ in ops
                            if not is_collective(n)), lo, hi))
        exposed_ns += length(subtract(coll, other))
        for n, a, b, _ in ops:
            op_time[n] += min(b, hi) - max(a, lo)
        gap_list += [(b - a, _label((a, b), spans))
                     for a, b in gaps(busy, lo, hi)]
    k = len(planes)
    top_ops = sorted(op_time.items(), key=lambda t: -t[1])[:TOP]
    top_gaps = sorted(gap_list, key=lambda t: -t[0])[:TOP]
    return {"busy_s": busy_ns / k * 1e-9, "window_s": (hi - lo) * 1e-9,
            "conv_s": conv_ns * 1e-9, "collective_exposed_s":
            exposed_ns / k * 1e-9,
            "breakdown": {
                "device_ops": [[n, t / k * 1e-9] for n, t in top_ops],
                "idle_gaps": [[label, t * 1e-9] for t, label in top_gaps]}}
