"""A run of each small stand-in cell, with the chip check skipped,
comes out ``correct`` as it stands, and not ``correct`` with its timed
path broken underneath: a step that returns its state unchanged, half
of the batch left out, the exchange between chips left out; and with
the program's own bf16 path in its place (the control)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402
from benchmarks.chip import faults  # noqa: E402

SEED = 2 ** 31 + 977  # wider than 32 signed bits: any whole number is a seed


def _run(tmp_path, fault=None, **kw):
    import jax
    from benchmarks.chip import run

    spec, cell, base = small.write_base(tmp_path, **kw)
    return run.run_cell(spec, cell, SEED, 1.0, False, jax.devices()[:1],
                        base=base, fault=fault)


def test_sound_train_run_is_correct(tmp_path):
    r = _run(tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [faults.state_unchanged, faults.half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(tmp_path, fault):
    r = _run(tmp_path, fault=fault)
    assert not r["correct"], r["checks"]


def test_train_control_bf16_is_not_correct(tmp_path):
    r = _run(tmp_path, precision="bf16")
    assert not r["correct"], r["checks"]


_SPATIAL4 = textwrap.dedent("""
    import json, sys, tempfile
    sys.path.insert(0, {tests!r})
    import chipbench_small as small
    import jax
    import contextlib
    from benchmarks.chip import faults, run

    out = {{}}
    for broken in (False, True):
        ctx = faults.no_exchange() if broken else contextlib.nullcontext()
        with ctx, tempfile.TemporaryDirectory() as t:
            spec, cell, base = small.write_base(t, spatial=4)
            r = run.run_cell(spec, cell, {seed}, 1.0, False,
                             jax.devices()[:4], base=base)
        out[str(broken)] = [r["correct"], r["checks"]]
    print(json.dumps(out))
""")


def test_exchange_left_out_is_not_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([small.CHECKOUT, os.path.join(
                   small.CHECKOUT, "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", _SPATIAL4.format(tests=small.TESTS,
                                                seed=SEED)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    import json

    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["False"][0], out["False"][1]
    assert not out["True"][0], out["True"][1]
