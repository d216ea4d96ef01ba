"""chip_smoke.py's phases on CPU at the smoke size, and its refusals.

The script itself runs CosmoFlow-128 on a TPU; here the tests steer the
same phase functions to ``cosmoflow-smoke`` (the script has no option to
do so), and check that it refuses to run without a TPU or without the
rest of the repo."""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from repro import configs  # noqa: E402


def _smoke_cfg():
    return configs.get_smoke_config("cosmoflow-128")


def test_train_and_serve_phases(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    lines = []
    tr = chip_smoke.train_phase(_smoke_cfg(), ckpt, log=lines.append)
    assert len(tr["losses"]) == chip_smoke.STEPS
    assert np.all(np.isfinite(tr["losses"]))
    assert tr["compiles_after_warmup"] == 0
    assert np.isfinite(tr["eval_loss"])
    sv = chip_smoke.serve_phase(ckpt, log=lines.append)
    assert sv["requests"] == chip_smoke.REQUESTS
    assert sv["batches"] == chip_smoke.REQUESTS // chip_smoke.GLOBAL_BATCH
    assert sv["max_abs_diff"] <= chip_smoke.SERVE_TOL
    assert any("median step after warm-up" in ln for ln in lines)


def test_spatial_phase_on_forced_host_devices(multidevice):
    out = multidevice(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke
from repro import configs
r = chip_smoke.spatial_phase(configs.get_smoke_config('cosmoflow-128'))
assert r['max_rel_diff'] <= chip_smoke.SPATIAL_REL_TOL, r
assert r['first_step_rel_diff'] <= chip_smoke.FIRST_STEP_REL_TOL, r
print('OK', r['first_step_rel_diff'], r['max_rel_diff'])
""", devices=4)
    assert "OK" in out


def _run_script(script, cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu(tmp_path):
    proc = _run_script(os.path.join(REPO, "chip_smoke.py"), REPO,
                       {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path / "chip_smoke.py"), str(tmp_path),
                       {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """The env var wins untouched; otherwise the cache sits at the fixed
    ``<checkout>/.jax_cache``."""
    from repro.api import cli

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cli.use_compile_cache() == str(tmp_path)
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = os.path.join(REPO, ".jax_cache")
        assert cli.use_compile_cache() == path
        assert updates == [("jax_compilation_cache_dir", path)]
