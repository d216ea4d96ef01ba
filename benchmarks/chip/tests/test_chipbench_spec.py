"""``BENCHMARK.json`` is well formed and every name in it resolves to its
file; a run without a TPU stops before any phase."""
from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402
from benchmarks.chip import harness  # noqa: E402

SPEC = harness.benchmark_spec(small.CHECKOUT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_command_and_paths_stay_inside_the_benchmark():
    assert SPEC["paths"] == ["benchmarks/chip"]
    script = SPEC["command"][1]
    assert script.startswith(SPEC["paths"][0] + "/")
    assert os.path.isfile(os.path.join(small.CHECKOUT, script))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_its_files_by_name(cell):
    files = harness.cell_files(cell)
    for path in files.values():
        assert os.path.isfile(path), path
    cfg = harness.load_json(files["config"])
    assert cfg["name"] == cell["config"]
    assert cfg["layout"]["chips"] == cell["chips"]
    traffic = harness.load_json(files["traffic"])
    from benchmarks.chip import jobs

    assert traffic["kind"] in jobs.DRIVERS
    limits = harness.load_json(files["limits"])
    assert limits and all(v > 0 for v in limits.values())
    for m in harness.cell_metrics(SPEC, cell, trace=True):
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_names_its_file(cfg):
    path = os.path.join(small.CHECKOUT, cfg["file"])
    assert path == os.path.join(harness.HERE, "configs", cfg["name"] + ".json")
    data = harness.load_json(path)
    assert data["reduced"] == cfg["reduced"]
    assert any(c["config"] == cfg["name"] for c in SPEC["workloads"])


def test_names_and_units_use_the_allowed_characters():
    names = ([m["name"] for m in METRICS]
             + [c["name"] for c in SPEC["workloads"]]
             + [c["name"] for c in SPEC["configs"]]
             + [c["traffic"] for c in SPEC["workloads"]])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (METRICS, SPEC["workloads"], SPEC["configs"]):
        keys = [x["name"] for x in group]
        assert len(keys) == len(set(keys))


def test_each_cell_reports_setup_another_metric_and_a_layer():
    for cell in SPEC["workloads"]:
        e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, False)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert harness.cell_metrics(SPEC, cell, True), cell["name"]


def test_per_layer_cells_report_the_metric_they_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)
        assert "\n" not in m["layer"] and m["layer"] in layers


def test_bounds_are_within_the_allowed_range():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]


def test_run_without_a_tpu_exits_before_any_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    cell = SPEC["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(small.CHECKOUT, SPEC["command"][1]),
         "--workload", cell, "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=small.CHECKOUT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "TPU" in proc.stderr
    assert not (tmp_path / "cache").exists()
