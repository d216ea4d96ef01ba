#!/usr/bin/env python3
"""Readings that set a cell's limits, made on the chip at the cell's own
size; the benchmark's own runs never run this.

    python3 benchmarks/chip/control.py --workload <cell> --first-seed <n> \\
        [--sound 12] [--faulty 3]

For ``--sound`` seeds it reads the compared numbers of sound runs of the
program (the lower readings). For the first ``--faulty`` of them it also
reads the control, the program's own bf16 path in its place, and, for a
each fault of ``faults.py`` that the cell can have: half of the batch
left out, and across chips the exchange left out. (A state left
unchanged reads 1 by construction.) Training needs no window. Each seed
prints one JSON line; the last line gives, per number and variant, the largest
sound reading and the smallest of each other.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (_CHECKOUT, os.path.join(_CHECKOUT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import check, faults, harness, jobs  # noqa: E402


def _bf16(cfg: dict) -> dict:
    return dict(cfg, precision=dict(cfg["precision"], program="bf16"))


def train_seed(cfg, traffic, seed, devices, variants) -> dict:
    data = jobs.TrainData(cfg, traffic, seed)
    progs = {}
    for name in variants:
        c, fault, ctx = cfg, None, contextlib.nullcontext()
        if name == "control_bf16":
            c = _bf16(cfg)
        elif name == "half_batch":
            fault = faults.half_batch
        elif name == "no_exchange":
            ctx = faults.no_exchange()
        with ctx:
            session, loader, _, checked, prog = jobs.train_program(
                c, traffic, data, fault)
            session.close()
        del session, loader
        gc.collect()
        progs[name] = prog
    ref = jobs.train_reference(cfg, data, checked, devices)
    data.close()
    return {name: check.train_numbers(p, ref) for name, p in progs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--faulty", type=int, default=3)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)
    files = harness.cell_files(cell)
    cfg = harness.load_json(files["config"])
    traffic = harness.load_json(files["traffic"])

    from repro.api import cli

    cli.use_compile_cache()
    devices = harness.require_chips(cell["chips"])
    worst: dict = {}
    for i in range(args.sound):
        seed = args.first_seed + i
        variants = ["sound"]
        if i < args.faulty:
            variants += ["control_bf16", "half_batch"]
            if cell["chips"] > 1:
                variants.append("no_exchange")
        t0 = time.perf_counter()
        out = train_seed(cfg, traffic, seed, devices, variants)
        print(json.dumps({"seed": seed, "s": time.perf_counter() - t0,
                          "numbers": out}), flush=True)
        for name, nums in out.items():
            for k, v in nums.items():
                if not isinstance(v, float):
                    continue
                key = f"{k}.{name}"
                pick = max if name == "sound" else min
                worst[key] = v if key not in worst else pick(worst[key], v)
    print(json.dumps({"summary": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
