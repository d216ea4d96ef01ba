"""Small stand-ins for the benchmark's cells, for the CPU tests: the
same files, drivers and checks, at a size a test run holds."""
from __future__ import annotations

import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(os.path.dirname(CHIP))
for _p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# five blocks, so the strided fourth conv is there; three pools
SMALL_MODEL = {"arch": "cosmoflow", "input_width": 32, "in_channels": 2,
               "out_dim": 4, "conv_channels": [4, 8, 16, 16, 16],
               "kernel_size": 3, "fc_dims": [64, 32], "batchnorm": True}


def smoke_model() -> dict:
    """The program's ``cosmoflow-smoke`` sizes, as a configuration's
    ``model`` group."""
    from repro.configs.cosmoflow import SMOKE

    return {"arch": "cosmoflow", "input_width": SMOKE.input_width,
            "in_channels": SMOKE.in_channels, "out_dim": SMOKE.out_dim,
            "conv_channels": list(SMOKE.conv_channels),
            "kernel_size": SMOKE.kernel_size, "fc_dims": list(SMOKE.fc_dims),
            "batchnorm": SMOKE.batchnorm}


def small_config(spatial: int = 1, precision: str = "auto") -> dict:
    cfg = json.load(open(os.path.join(CHIP, "configs", "cosmoflow-128.json")))
    cfg["name"] = "cosmoflow-small"
    cfg["model"] = dict(SMALL_MODEL)
    cfg["layout"] = {"chips": spatial, "data": 1, "spatial": spatial}
    cfg["precision"] = dict(cfg["precision"], program=precision)
    return cfg


def write_base(tmp, *, spatial=1, precision="auto", limits=None) -> tuple:
    """A benchmark directory under ``tmp`` holding one small training
    cell; returns (spec, cell, base)."""
    base = str(tmp)
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(base, d), exist_ok=True)
    os.symlink(os.path.join(CHIP, "metrics"), os.path.join(base, "metrics"))
    cfg = small_config(spatial, precision)
    traffic = {"kind": "train", "global_batch": 2, "epoch_samples": 64,
               "volumes": 6, "prefetch": 2, "checked_steps": 3}
    lim = limits or {"loss1_gap": 1e-4, "grad_gap": 1e-4, "update_gap": 1e-3}
    name = "small.train"
    cell = {"name": name, "config": cfg["name"], "traffic": name,
            "chips": spatial, "why": "test"}
    for d, key, obj in (("configs", cfg["name"], cfg),
                        ("traffic", name, traffic), ("limits", name, lim)):
        with open(os.path.join(base, d, key + ".json"), "w") as f:
            json.dump(obj, f)
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    spec = dict(spec, workloads=[cell])
    return spec, cell, base
