"""The owners of device time and idle time (``attribution.py``): on
hand-made planes, and on the trace recorded on the chip from
``cf128.train.b4`` before the program had named scopes."""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402
from benchmarks.chip import attribution, xplane  # noqa: E402


@pytest.mark.parametrize("op_name,scope", [
    ("jit(local_step)/jvp(block0)/conv/conv_general_dilated", "conv"),
    ("jit(local_step)/transpose(jvp(block1))/pool/select_and_scatter",
     "pool"),
    ("jit(local_step)/jvp(block1)/conv/halo/ppermute", "halo"),
    ("jit(local_step)/transpose(jvp(grad_comm))/psum", "grad_comm"),
    ("jit(local_step)/jvp(head)/reshard/all_to_all", "reshard"),
    ("jit(local_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(local_step)/transpose(jvp(loss))/mul;jit(local_step)/pool/add",
     "loss"),
    ("jit(local_step)/jvp()/conv_general_dilated", "unscoped"),
    ("jit(local_step)/jvp(block0)/convolve/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_takes_the_innermost_scope(op_name, scope):
    assert attribution.scope_of(op_name) == scope


HLO = """HloModule m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %convolution.3 = f32[4]{0} convolution(%p, %p), window={size=1}, metadata={op_name="jit(step)/jvp(block0)/conv/conv_general_dilated"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kOutput, calls=%fused, metadata={op_name="jit(step)/jvp(block0)/conv/conv_general_dilated"}
  %copy.2 = f32[4]{0} copy(%fusion.1)
  ROOT %select-and-scatter.4 = f32[4]{0} select-and-scatter(%copy.2), metadata={op_name="jit(step)/transpose(jvp(block0))/pool/select_and_scatter" source_file="m.py"}
}
"""


def test_instruction_scopes_read_each_instructions_metadata():
    assert attribution.instruction_scopes(HLO) == {
        "p": "unscoped", "convolution.3": "conv", "x": "unscoped",
        "fusion.1": "conv", "copy.2": "unscoped",
        "select-and-scatter.4": "pool"}


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, ops):
    step = _ev("jit_step(1)", 0, 2000)
    return NS(name=name, lines=[NS(name=xplane.OPS_LINE, events=ops),
                                NS(name=xplane.MODULES_LINE, events=[step])])


SCOPES = {"jit_step(1)": attribution.instruction_scopes(HLO)}


def _fake():
    # window [100, 1100) on the profiler clock; the anchor at 50, the
    # program's clock reads 0 there
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev(xplane.ANCHOR, 50, 1)])])
    dev0 = _plane("/device:TPU:0", [
        _ev("%fusion.1 = f32[4]{0} fusion()", 50, 250),   # 200 in window
        _ev("copy.2", 300, 100),
        _ev("select-and-scatter.4", 700, 100),
        _ev("add.9", 1000, 200)])                          # 100 in window
    dev1 = _plane("/device:TPU:1", [
        _ev("%fusion.1 = f32[4]{0} fusion()", 100, 400),
        _ev("select-and-scatter.4", 500, 200)])
    return NS(planes=[host, dev0, dev1])


# program clock = profiler clock - 50. The step thread: a step, a wait
# on the queue, then a synchronous load whose read sits inside it. The
# worker's long load overlaps every gap and must not take them.
SPANS = [("train.step", 50, 300, "MainThread"),
         ("io.wait", 350, 250, "MainThread"),
         ("io.load", 800, 300, "MainThread"),
         ("io.read", 850, 100, "MainThread"),
         ("io.load", 0, 1100, "io-prefetch_0")]


def _reduce(spans):
    return attribution.reduce("fake", 2, spans, 0, (50, 1050),
                              data=_fake(), scopes=SCOPES)


def test_scope_seconds_sum_clipped_op_time_per_scope():
    r = _reduce(SPANS)
    # dev0: conv 200, copy 100, pool 100, add 100; dev1: conv 400, pool 200
    assert r["scope_s"] == {"conv": pytest.approx(300e-9),
                            "pool": pytest.approx(150e-9),
                            "unscoped": pytest.approx(100e-9)}
    # the same op time ``xplane.reduce`` sums for its device_ops
    ops = xplane.reduce("fake", 2, [s[:3] for s in SPANS], 0, (50, 1050),
                        data=_fake(), conv={})["breakdown"]["device_ops"]
    assert sum(r["scope_s"].values()) == pytest.approx(sum(t for _, t in ops))


def test_idle_goes_to_the_innermost_step_thread_span():
    r = _reduce(SPANS)
    # dev0 gaps: 400-700, 800-1000; dev1 gaps: 100 is busy to 700,
    # then 700-1100. Step thread, profiler clock: train.step 100-400,
    # io.wait 400-650, io.load 850-1150 with io.read 900-1000.
    # dev0: 400-650 io.wait 250, 650-700 none 50, 800-850 none 50,
    #       850-900 io.load 50, 900-1000 io.read 100
    # dev1: 700-850 none 150, 850-900 io.load 50, 900-1000 io.read 100,
    #       1000-1100 io.load 100
    assert r["idle_under_s"] == {"io.wait": pytest.approx(125e-9),
                                 "none": pytest.approx(125e-9),
                                 "io.load": pytest.approx(100e-9),
                                 "io.read": pytest.approx(100e-9)}
    idle = xplane.reduce("fake", 2, [s[:3] for s in SPANS], 0, (50, 1050),
                         data=_fake(), conv={})
    assert sum(r["idle_under_s"].values()) == pytest.approx(
        idle["window_s"] - idle["busy_s"])


def test_spans_without_threads_are_one_thread():
    spans = [s[:3] for s in SPANS if s[3] == "MainThread"]
    assert _reduce(spans)["idle_under_s"] == _reduce(SPANS)["idle_under_s"]


def test_input_spans_split_a_load_into_read_place_and_self_time():
    spans = [("io.load", 0, 100, "w", {"samples": 2}),
             ("io.read", 10, 30, "w", {"bytes": 64}),
             ("io.read", 40, 20, "w", {"bytes": 64}),
             ("io.place", 70, 20, "w", {"bytes": 130}),
             ("io.read", 50, 40, "other", {"bytes": 64}),  # another load
             ("io.place", 150, 20, "w", {"bytes": 130}),  # load untraced
             ("io.load", 200, 100, "w", {"samples": 2}),  # place untraced
             ("io.read", 210, 30, "w", {"bytes": 64}),
             ("io.wait", 0, 100, "main", None)]
    io = attribution.input_spans(spans)
    assert io["batches"] == 1
    assert io["read_bytes"] == 128 and io["place_bytes"] == 130
    assert io["read_s"] == pytest.approx(50e-9)
    assert io["place_s"] == pytest.approx(20e-9)
    assert io["self_s"] == pytest.approx(30e-9)


RECORDED = os.path.join(small.CHIP, "recorded", "cf128.train.b4")


def _recorded(module):
    with open(RECORDED + ".host.json") as f:
        host = json.load(f)
    return module.reduce(RECORDED + ".xplane.pb", host["devices"],
                         host["spans"], host["anchor_ns"],
                         tuple(host["window_ns"]))


def test_recorded_trace_reads_as_before_and_is_all_unscoped():
    """The trace predates the named scopes: every operation is
    ``unscoped``, all idle time is under no span (none were kept), and
    the reduction the benchmark's readers use reads what it always has."""
    r = _recorded(xplane)
    assert r == {
        "busy_s": 0.8082797500000001, "window_s": 0.9372110360000001,
        "conv_s": 0.17264009000000002, "collective_exposed_s": 0.0,
        "breakdown": {
            "device_ops": [
                ["select-and-scatter.4", 0.129367863],
                ["fusion.144", 0.084470659], ["copy.405", 0.044265327],
                ["copy.407", 0.040778724], ["fusion.1", 0.040016413],
                ["reduce-window.5", 0.039898608],
                ["fusion.326", 0.027331189000000002],
                ["copy.371", 0.025528349000000002],
                ["copy.367", 0.024850881000000002],
                ["copy.409", 0.02477748]],
            "idle_gaps": [
                ["none", 0.047850540000000004], ["none", 0.025978739],
                ["none", 0.017348159000000002],
                ["none", 0.013819807000000002], ["none", 0.012409041],
                ["none", 0.011432576], ["none", 1.3713e-05],
                ["none", 6.604000000000001e-06],
                ["none", 6.5580000000000006e-06],
                ["none", 6.538000000000001e-06]]}}
    a = _recorded(attribution)
    assert set(a["scope_s"]) == {"unscoped"}
    assert a["scope_s"]["unscoped"] == pytest.approx(r["busy_s"], rel=1e-12)
    assert set(a["idle_under_s"]) == {"none"}
    assert a["idle_under_s"]["none"] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
