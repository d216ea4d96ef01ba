#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its limits and its
per-layer readers are found by name from ``BENCHMARK.json`` (see
``harness.py``). Set-up (compile, weights, the loader's store, the
checked first steps or the warm-up requests) runs from process start to
the first timed step and is reported as ``setup_s``; then the cell's
traffic runs for ``--seconds``. ``--trace 1`` adds a short profiled
stretch after the window and reports the per-layer metrics instead of
the end-to-end ones. Last of all the results are compared with the plain
reference (``reference.py``, ``check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; its last
key, ``checks``, holds each compared number beside its limit, which are
also the last lines of stderr. Without a TPU, or with fewer chips than
the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (_CHECKOUT, os.path.join(_CHECKOUT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import check, harness, jobs  # noqa: E402

EXIT_NO_CHIP = 3


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _finite(obj):
    """JSON has no infinity: a number that is not finite prints as its
    name."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj


def run_cell(spec: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices, *, t_process: float = T_PROCESS, base: str = harness.HERE,
             fault=None, compiles=None, keep_trace=None) -> dict:
    """Drive ``cell`` once on ``devices`` and return the result object."""
    files = harness.cell_files(cell, base)
    cfg = harness.load_json(files["config"])
    traffic = harness.load_json(files["traffic"])
    limits = harness.load_json(files["limits"])
    compiles = compiles if compiles is not None else harness.CompileCounter()
    driver = jobs.DRIVERS[traffic["kind"]]
    out = driver(cfg, traffic, seed, seconds, trace, t_process, devices,
                 compiles, log=_log, fault=fault, keep_trace=keep_trace)
    verdict = check.judge(out["numbers"], limits)
    wanted = harness.cell_metrics(spec, cell, trace)
    metrics = {}
    if trace:
        ctx = dict(out["ctx"], log=_log,
                   peaks=harness.peaks(devices[0].device_kind))
        for m in wanted:
            v = harness.metric_reader(m["name"], base)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in wanted:
            v = out["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = harness.device_info(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": verdict["ok"] and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace:
        tr = out["ctx"]["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    result["checks"] = verdict["checks"]
    _log(f"compiles in the window: {out['window_compiles']}")
    _log("readings: " + json.dumps(
        {k: v for k, v in out["numbers"].items() if k not in limits}))
    for name, c in verdict["checks"].items():
        _log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the profiler trace of a traced run "
                         "into DIR")
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)

    from repro.api import cli

    cli.use_compile_cache()
    try:
        devices = harness.require_chips(cell["chips"])
        harness.peaks(devices[0].device_kind)
    except (harness.NoChip, KeyError) as e:
        _log(f"run.py: {e}")
        return EXIT_NO_CHIP
    result = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                      devices, keep_trace=args.keep_trace)
    print(json.dumps(_finite(result), allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
