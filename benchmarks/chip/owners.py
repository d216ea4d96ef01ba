#!/usr/bin/env python3
"""Who owns a training cell's device time and idle time, on the chip;
the benchmark's own runs never run this.

    python3 benchmarks/chip/owners.py --workload <cell> --seed <n>

After the cell's set-up it runs ``PAIRS`` pairs of untraced windows of
``SECONDS``, one with the program's span tracer on and one with it off
(in turn first), and prints the samples per second of each: the cost of
the spans. Then it traces a stretch as ``--trace 1`` does, reduces it
with ``xplane.reduce`` and ``attribution.reduce`` (host spans with their
thread names), and times ``PLACE_BATCHES`` batches' placement to its
end (``placement``). It prints one JSON line with the cell's per-layer
metrics, those of ``NEW_METRICS``, and the whole ``scope_s``,
``idle_under_s``, input-span totals and placement times they are read
from.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (_CHECKOUT, os.path.join(_CHECKOUT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import (attribution, flops, harness, jobs,  # noqa: E402
                             xplane)

NEW_METRICS = ("io_exposed_ms.train", "io_read_ms.train",
               "io_place_ms.train", "pool_ms.train", "norm_ms.train")
PAIRS = 3
SECONDS = 10.0
PLACE_BATCHES = 8


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def window(session, loader, ids, seconds: float) -> dict:
    """A closed loop like the cell's window; at most two steps in
    flight."""
    stall0 = session.telemetry().get("io_stall_s", 0.0)
    steps, prev = 0, None
    t0 = time.perf_counter()
    while time.perf_counter() < t0 + seconds:
        loss = session.step(loader.load_batch(next(ids)))
        if prev is not None:
            prev.block_until_ready()
        prev, steps = loss, steps + 1
    prev.block_until_ready()
    return {"steps": steps, "window_s": time.perf_counter() - t0,
            "io_stall_s": session.telemetry().get("io_stall_s", 0.0)
            - stall0}


def host_spans(rec: dict) -> list:
    """(name, start ns, duration ns, thread name, attributes) of each
    span the traced stretch recorded, on the ``perf_counter_ns`` clock."""
    return [(e.name, e.ts_ns + rec["tracer"].epoch_ns, e.dur_ns, e.thread,
             e.attrs) for e in rec["tracer"].events() if e.dur_ns is not None]


def traced_stretch(session, loader, ids, n_devices: int) -> dict:
    """``jobs.TRACE_STEPS`` steps under the profiler, reduced; as in the
    cell's traced run, the step before them has finished when the
    stretch starts."""
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as td:
        prev = session.step(loader.load_batch(next(ids)))
        prev.block_until_ready()
        with jobs.traced(td) as rec:
            t_a = time.perf_counter_ns()
            for _ in range(jobs.TRACE_STEPS):
                loss = session.step(loader.load_batch(next(ids)))
                prev.block_until_ready()
                prev = loss
            prev.block_until_ready()
            rec["window"] = (t_a, time.perf_counter_ns())
        spans = host_spans(rec)
        path = xplane.find_xplane(td)
        clock = (rec["anchor_ns"], rec["window"])
        trace = dict(
            xplane.reduce(path, n_devices, [s[:3] for s in spans], *clock),
            **attribution.reduce(path, n_devices, spans, *clock))
    return {"trace": trace, "host_spans": spans}


def placement(loader, ids) -> dict:
    """``io.place`` against the transfer's end, in ms per batch: a
    synchronous loader places ``PLACE_BATCHES`` batches on this thread
    with the device idle, and ``jax.block_until_ready`` follows each. The
    span closes when ``make_array_from_callback`` returns, the last thing
    a load does, so the transfer ends ``wait`` after it (a put may return
    once its copy is enqueued)."""
    import jax
    from repro.obs import trace as trace_lib

    tracer = trace_lib.enable()
    waits = []
    for _ in range(PLACE_BATCHES):
        batch = loader.load_batch(next(ids))
        t = time.perf_counter_ns()
        jax.block_until_ready(batch)
        waits.append((time.perf_counter_ns() - t) * 1e-6)
    trace_lib.disable(tracer)
    spans = [e.dur_ns * 1e-6 for e in tracer.events()
             if e.name == "io.place" and e.dur_ns is not None]
    return {"place_ms": statistics.median(spans),
            "wait_ms": statistics.median(waits),
            "done_ms": statistics.median(a + b for a, b in zip(spans, waits))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)
    files = harness.cell_files(cell)
    cfg = harness.load_json(files["config"])
    traffic = harness.load_json(files["traffic"])

    from repro.api import cli
    from repro.obs import trace as trace_lib

    cli.use_compile_cache()
    devices = harness.require_chips(cell["chips"])
    data = jobs.TrainData(cfg, traffic, args.seed)
    session, loader, ids, _, _ = jobs.train_program(cfg, traffic, data)
    gb = traffic["global_batch"]
    rates = {"off": [], "on": []}
    for p in range(PAIRS):
        for mode in (("on", "off") if p % 2 else ("off", "on")):
            tracer = trace_lib.enable() if mode == "on" else None
            w = window(session, loader, ids, SECONDS)
            if tracer is not None:
                trace_lib.disable(tracer)
            rates[mode].append(w["steps"] * gb / w["window_s"])
            _log(f"window, spans {mode}: {rates[mode][-1]:.4f} samples/s")
    out = traced_stretch(session, loader, ids, len(devices))
    loader.close()  # its workers would share the host with ``placement``
    sync = session.make_loader(data.root, seed=data.seed_order, prefetch=0,
                               cache=False)
    place = placement(sync, ids)
    sync.close()
    session.close()
    data.close()

    m = cfg["model"]
    ctx = dict(out, **w, chips=len(devices), train=True, global_batch=gb,
               train_flops=flops.train_flops(m),
               conv_flops=flops.conv_flops(m, True),
               conv_bytes=flops.conv_bytes(m, True),
               trace_steps=jobs.TRACE_STEPS, log=_log,
               peaks=harness.peaks(devices[0].device_kind))
    names = [x["name"] for x in harness.cell_metrics(spec, cell, True)]
    metrics = {n: harness.metric_reader(n)(ctx)
               for n in names + list(NEW_METRICS)}
    med = {k: statistics.median(v) for k, v in rates.items()}
    print(json.dumps({
        "seed": args.seed, "device": harness.device_info(devices),
        "samples_per_s": rates, "median": med,
        "span_cost": 1 - med["on"] / med["off"],
        "metrics": metrics, "input": attribution.input_spans(
            out["host_spans"]),
        "placement": place,
        "busy_s": out["trace"]["busy_s"],
        "window_s": out["trace"]["window_s"],
        "scope_s": out["trace"]["scope_s"],
        "idle_under_s": out["trace"]["idle_under_s"],
        "breakdown": out["trace"]["breakdown"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
