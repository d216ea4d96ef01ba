"""The plain reference (``reference.py``) against the system's forward,
loss and gradients at ``cosmoflow-smoke`` sizes, both at "highest"
precision: on one device, and on four forced host devices at
spatial=4, where the halo exchange and the overlap stitch run."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_small as small  # noqa: E402

TOL = 1e-4  # float32 sums in another order; a lost halo moves them ~1e-2

_COMPARE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    import chipbench_small as small
    import jax, jax.numpy as jnp, numpy as np
    from benchmarks.chip import jobs, reference
    from repro.api import RunConfig, compile

    cfg = {{"name": "cosmoflow-smoke", "model": small.smoke_model()}}
    m = cfg["model"]
    w, c = m["input_width"], m["in_channels"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, w, w, w, c), dtype=np.float32))
    y = jnp.asarray(rng.standard_normal((2, m["out_dim"]), dtype=np.float32))
    with jax.default_matmul_precision("highest"):
        s = compile(RunConfig(model=jobs.model_config(cfg), global_batch=2,
                              spatial={spatial}))
        s.params = jobs.place_weights(cfg, 11, s.params)
        eval_loss, pred = s.evaluate(x, y)
        loss = s.step(x, y)
        grads = {{k: v / 0.1 for k, v in s.opt_state.m.items()}}
        p = jax.jit(lambda k: reference.init_params(k, m))(
            jax.random.PRNGKey(11))
        r_pred = reference.forward(p, x, m)
        r_eval = jnp.mean(jnp.mean(jnp.square(r_pred - y), axis=-1))
        r_loss, r_grads = jax.value_and_grad(reference.mse)(p, x, y, m, 0)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    print(json.dumps({{
        "pred": rel(pred, r_pred), "eval_loss": rel(eval_loss, r_eval),
        "loss": rel(loss, r_loss),
        "grads": max(rel(grads[k], r_grads[k]) for k in r_grads)}}))
""")


def _compare(spatial: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([small.CHECKOUT, os.path.join(
                   small.CHECKOUT, "src")]))
    if spatial > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={spatial}"
    proc = subprocess.run(
        [sys.executable, "-c", _COMPARE.format(tests=small.TESTS,
                                               spatial=spatial)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_reference_matches_the_system_on_one_device():
    gaps = _compare(1)
    assert max(gaps.values()) <= TOL, gaps


def test_reference_matches_the_system_at_spatial_4():
    gaps = _compare(4)
    assert max(gaps.values()) <= TOL, gaps
