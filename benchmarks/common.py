"""Shared benchmark plumbing.

Every e2e bench on this oversubscribed 2-core box fights the same
enemy: machine drift. The cure is the same everywhere — time all cells
in interleaved rounds so a load spike hits every cell equally, then
take a trimmed mean — so the helper lives here once instead of being
re-derived per bench (it used to be copy-pasted across the api,
resilience, grad_comm and conv_overlap benches).

Two trims, both deliberate:

- ``trim="ends"`` (default): drop the top and bottom fifth, mean the
  core. Right for paired overhead measurements (guarded vs unguarded,
  session vs raw) where the headline is a ratio of two means and both
  tails are noise.
- ``trim="best"``: keep only the best third. Load spikes on a shared
  box are one-sided (nothing ever runs *faster* than the quiet-machine
  time), so the best third is the least-contended estimate — right for
  absolute step times compared across configurations.

``run_rows_subprocess`` is the other shared pattern: multi-device
benches fork a child on forced-host CPU devices
(``--xla_force_host_platform_device_count``; the parent keeps the real
1-device CPU backend) and the child reports ``ROW,name,us,derived``
lines that the parent forwards to ``emit``; a failed child fails the
run.

Timed cells also emit ``bench.<name>`` spans through the §14 tracer
(no-ops unless a bench activated one), and every BENCH_*.json row
carries a ``trace_path`` provenance field — the trace the timing ran
under, or None — schema-checked here by ``validate_rows``.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

from repro.obs import trace as trace_lib

# The BENCH_*.json row schema. ``validate_rows`` is the write gate:
# every row the harness dumps must carry exactly these keys.
ROW_KEYS = ("name", "us_per_call", "derived", "trace_path")


def validate_rows(rows: List[dict]) -> None:
    """Schema-check BENCH_*.json rows; raises ValueError naming the bad
    row. name/derived are strings, us_per_call numeric, trace_path a
    path string or None."""
    for i, row in enumerate(rows):
        if set(row) != set(ROW_KEYS):
            raise ValueError(
                f"row {i}: keys {sorted(row)} != schema {sorted(ROW_KEYS)}")
        if not isinstance(row["name"], str) or not row["name"]:
            raise ValueError(f"row {i}: name must be a non-empty string")
        if not isinstance(row["us_per_call"], (int, float)) or isinstance(
                row["us_per_call"], bool):
            raise ValueError(f"row {i} ({row['name']}): us_per_call must "
                             f"be numeric, got {row['us_per_call']!r}")
        if not isinstance(row["derived"], str):
            raise ValueError(f"row {i} ({row['name']}): derived must be a "
                             f"string")
        tp: Optional[str] = row["trace_path"]
        if tp is not None and (not isinstance(tp, str) or not tp):
            raise ValueError(f"row {i} ({row['name']}): trace_path must "
                             f"be a non-empty path string or None")


def trimmed_mean_us(samples: List[float], *, trim: str = "ends") -> float:
    """Trimmed mean of per-call seconds, in microseconds."""
    v = sorted(samples)
    if trim == "best":
        k = max(len(v) // 3, 1)  # best third: load spikes are one-sided
        return sum(v[:k]) / k * 1e6
    k = max(len(v) // 5, 1)
    core = v[k:-k] or v
    return sum(core) / len(core) * 1e6


def interleaved_trimmed(calls: Dict[str, Callable[[], object]],
                        rounds: int, *, trim: str = "ends",
                        warmups: int = 1) -> Dict[str, float]:
    """Time all calls in interleaved rounds -> {name: trimmed-mean us}.

    Each call must block until its work is done (wrap in
    ``jax.block_until_ready``). ``warmups`` un-timed calls per cell
    absorb jit compilation (use 2 when donation means the second call
    compiles a differently-placed variant).
    """
    for c in calls.values():
        for _ in range(warmups):
            c()
    samples: Dict[str, List[float]] = {k: [] for k in calls}
    for _ in range(rounds):
        for k, c in calls.items():
            # the span brackets exactly the timed region, so a bench
            # run under an active tracer shows its cells as bench.*
            # tracks (no-op — NULL_SPAN — otherwise)
            with trace_lib.span(f"bench.{k}"):
                t0 = time.perf_counter()
                c()
                samples[k].append(time.perf_counter() - t0)
    return {k: trimmed_mean_us(v, trim=trim) for k, v in samples.items()}


def run_rows_subprocess(script: str, emit: Callable[[str, float, str], None],
                        *, errname: str, devices: int = 4,
                        timeout: int = 900) -> None:
    """Run ``script`` in a child python on ``devices`` forced-host CPU
    devices and forward its ``ROW,name,us,derived`` stdout lines to
    ``emit``. For CPU devices only: the child runs with
    ``JAX_PLATFORMS=cpu``, because on a chip host the parent, which has
    touched JAX, holds the chip and a child that needs it fails or hangs.
    A child that fails or times out raises ``RuntimeError`` naming
    ``errname``, so the bench run exits non-zero. The child's PYTHONPATH
    gets both ``src`` and the repo root (so scripts can import this
    module)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep + root
                         + os.pathsep + env.get("PYTHONPATH", ""))
    try:
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"{errname} bench child timed out after "
                           f"{timeout}s") from e
    if proc.returncode != 0:
        raise RuntimeError(f"{errname} bench child failed "
                           f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            emit(name, float(us), derived)
