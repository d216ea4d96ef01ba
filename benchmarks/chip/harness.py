"""What every cell shares: finding a cell's files by name, the device and
its peaks, seeds, the compile counter and the memory reading.

A cell (``BENCHMARK.json`` -> ``workloads``) names a configuration and a
traffic mix. Their files are found by name alone:

- ``configs/<config>.json``: the model, its layout over chips, precision
  and optimizer, as run;
- ``traffic/<traffic>.json``: the job and its parameters, read by the
  general drivers in ``jobs.py``;
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``;
- ``metrics/<metric>.py``: one reader per per-layer metric.

So a later change adds a cell by adding files and entries, and edits
none.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: str = CHECKOUT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in spec['workloads']]}")


def cell_files(cell: dict, base: str = HERE) -> Dict[str, str]:
    """Paths of the cell's configuration, traffic and limits files."""
    return {"config": os.path.join(base, "configs", cell["config"] + ".json"),
            "traffic": os.path.join(base, "traffic", cell["traffic"] + ".json"),
            "limits": os.path.join(base, "limits", cell["name"] + ".json")}


def metric_file(name: str, base: str = HERE) -> str:
    return os.path.join(base, "metrics", name + ".py")


def metric_reader(name: str, base: str = HERE) -> Callable:
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    path = metric_file(name, base)
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: dict, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def peaks(kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown device is
    an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform!r} device(s)")
    return devices[:n]


def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit seeds from a seed of any size."""
    ss = np.random.SeedSequence(int(seed))
    return [int(s) & 0x7FFFFFFF for s in ss.generate_state(n)]


class CompileCounter:
    """Counts XLA compiles in this process through JAX's monitoring
    events (a listener cannot be removed, so make one per process)."""

    def __init__(self):
        import jax

        self.durations: List[float] = []

        def listener(event, duration, **_):
            if event == COMPILE_EVENT:
                self.durations.append(duration)

        jax.monitoring.register_event_duration_secs_listener(listener)

    def __len__(self) -> int:
        return len(self.durations)


def memory_peak_bytes(devices) -> Optional[int]:
    """Highest over ``devices`` of ``peak_bytes_in_use`` (arrays held
    between programs) plus ``peak_bytes_reserved`` (a program's
    temporaries, where this runtime keeps them); ``None`` where the
    backend keeps no counters."""
    best = None
    for d in devices:
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" not in s:
            continue
        v = s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
        best = v if best is None else max(best, v)
    return best


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
