"""The trace reduction (``xplane.py``): on a hand-made two-device trace,
and on a small trace recorded on the chip from ``cf128.train.b4``."""
from __future__ import annotations

import glob
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402
from benchmarks.chip import xplane  # noqa: E402


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, ops):
    step = _ev("jit_step(1)", 0, 2000)
    return NS(name=name, lines=[NS(name=xplane.OPS_LINE, events=ops),
                                NS(name=xplane.MODULES_LINE, events=[step])])


CONV = {"jit_step(1)": {"fusion.1"}}


def _fake():
    # window [100, 1100) on the profiler clock; the anchor at 50
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev(xplane.ANCHOR, 50, 1)])])
    dev0 = _plane("/device:TPU:0", [
        _ev("%fusion.1 = f32[4]{0} fusion()", 100, 300),  # conv, 300
        _ev("collective-permute-start.2", 350, 150),  # 50 hidden, 100 bare
        _ev("add.3", 600, 100),
        _ev("all-reduce.4", 900, 100)])               # bare, 100
    dev1 = _plane("/device:TPU:1", [
        _ev("%fusion.1 = f32[4]{0} fusion()", 100, 500),
        _ev("all-reduce.4", 550, 100)])               # 50 bare
    return NS(planes=[host, dev0, dev1])


def test_hand_made_two_device_trace():
    spans = [("train.step", 0, 800), ("io.wait", 800, 300)]
    r = xplane.reduce("fake", 2, spans, 50, (100, 1100), data=_fake(),
                      conv=CONV)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: dev0 100-500, 600-700, 900-1000 = 600; dev1 100-650 = 550
    assert r["busy_s"] == pytest.approx(575e-9)
    assert r["conv_s"] == pytest.approx(800e-9)
    # exposed collectives: dev0 400-500 and 900-1000, dev1 600-650
    assert r["collective_exposed_s"] == pytest.approx(125e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # dev1's gap 650-1100 is mostly under io.wait; dev0's 500-600 under
    # train.step
    assert gaps[0] == ["io.wait", pytest.approx(450e-9)]
    assert ["train.step", pytest.approx(100e-9)] in gaps
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(400e-9)


HLO = """HloModule m

%fused_conv (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %convolution.3 = f32[4]{0} convolution(%p, %p), window={size=1}
}

%wrapper (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %fusion.9 = f32[4]{0} fusion(%q), kind=kOutput, calls=%fused_conv
}

%fused_add (r: f32[4]) -> f32[4] {
  %r = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%r, %r)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kOutput, calls=%fused_conv
  %call.2 = f32[4]{0} call(%x), to_apply=%wrapper
  %fusion.4 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_add
  ROOT %convolution.7 = f32[4]{0} convolution(%x, %x), window={size=1}
}
"""


def test_conv_instructions_follow_called_computations():
    conv = xplane.conv_instructions(HLO)
    assert {"fusion.1", "call.2", "convolution.7", "fusion.9",
            "convolution.3"} <= conv
    assert "fusion.4" not in conv and "add.1" not in conv


def test_union_and_subtract():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.gaps([(2, 4)], 0, 6) == [(0, 2), (4, 6)]


RECORDED = glob.glob(os.path.join(small.CHIP, "recorded", "*.xplane.pb"))
RECORDED_HOST = os.path.join(small.CHIP, "recorded", "cf128.train.b4.host.json")


def _recorded():
    import json

    with open(RECORDED_HOST) as f:
        host = json.load(f)
    path = os.path.join(small.CHIP, "recorded", "cf128.train.b4.xplane.pb")
    return xplane.reduce(path, host["devices"], host["spans"],
                         host["anchor_ns"], tuple(host["window_ns"]))


def test_recorded_chip_trace_reduces():
    """Six steps of ``cf128.train.b4`` traced on a TPU v5e chip (10 s
    window, seed 2147483901), with the host threads' lines other than
    the main thread's taken out to keep the file small; no program spans
    were kept with it, so its idle gaps are labelled ``none``."""
    r = _recorded()
    assert 0.5 < r["busy_s"] / r["window_s"] < 1.0
    # conv ops are found through the HLO the trace carries; they are a
    # real but minor part of the step on this layout
    assert 0.05 < r["conv_s"] / r["busy_s"] < 0.6
    assert r["collective_exposed_s"] == 0.0  # one chip, no collectives
    ops, gaps = r["breakdown"]["device_ops"], r["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= xplane.TOP and 0 < len(gaps) <= xplane.TOP
    for name, secs in ops + gaps:
        assert isinstance(name, str) and " = " not in name and secs > 0
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    known = {"train.step", "io.wait", "io.load", "io.load.sync", "none"}
    assert {label for label, _ in gaps} <= known
