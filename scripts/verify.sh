#!/usr/bin/env bash
# Per-PR verification: tier-1 tests + kernel perf smoke.
#
#   make verify            # or: bash scripts/verify.sh
#   bash scripts/verify.sh pipeline         # just the §13 pipeline gate
#   bash scripts/verify.sh obs              # just the §14 obs gate
#   bash scripts/verify.sh serve            # just the §15 serving gate
#   BENCH_OUT=BENCH_PR_N.json make verify   # also capture the bench rows
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

pipeline_gate() {
    echo "== pipeline gate =="
    # DESIGN.md §13: (a) the joint (data x spatial x pipeline) argmin
    # must never return a pipelined plan priced above the best
    # non-pipelined candidate (at a fixed device pool, pipelining adds a
    # bubble to equal compute — it wins capacity, not modeled time), and
    # (b) under a memory budget only the pipelined split fits, the
    # planner must pick it and its modeled peak must fit. Explicit exit,
    # not assert (PYTHONOPTIMIZE-safe).
    python - <<'EOF'
import sys

from repro import configs
from repro.core import memory, plan as plan_lib
from repro.core.perf_model import V100

cfg = configs.get_config("cosmoflow-512")
kw = dict(spatial_degree=1, data_degree=8, global_batch=32,
          grad_comm="overlap")
base = plan_lib.plan_convnet(cfg, V100, **kw)
cands = plan_lib.candidate_pipeline_plans(
    cfg, V100, pipeline_degrees=(2,), micro_batch_options=(8,),
    num_devices=8, global_batch=32)
joint = plan_lib.plan_convnet(cfg, V100, pipeline_options=(2,),
                              micro_batch_options=(8,), **kw)
if min(c.cost for c in cands) <= base.cost:
    sys.exit("pipeline gate: a pipelined candidate prices at or below "
             "pure data parallelism on equal devices — the bubble term "
             "vanished from the cost model")
if joint.n_groups != 1 or joint.cost != base.cost:
    sys.exit(f"pipeline gate: joint argmin picked {joint.name} "
             f"({joint.cost * 1e3:.0f}ms) over the cheaper non-pipelined "
             f"{base.name} ({base.cost * 1e3:.0f}ms)")
budget = 100 * 2 ** 30
chosen = plan_lib.plan_convnet(cfg, V100, memory_budget_bytes=budget,
                               pipeline_options=(2,),
                               micro_batch_options=(8,), **kw)
peak = memory.plan_peak_bytes(cfg, chosen, global_batch=32)
if chosen.n_groups < 2 or peak.total > budget:
    sys.exit(f"pipeline gate: budget {budget / 2 ** 30:.0f}GiB should "
             f"force a pipelined plan, got {chosen.name} at "
             f"{peak.total / 2 ** 30:.1f}GiB")
print(f"pipeline gate OK: joint argmin keeps {base.name} "
      f"({base.cost * 1e3:.0f}ms vs pipelined "
      f"{min(c.cost for c in cands) * 1e3:.0f}ms); "
      f"{budget / 2 ** 30:.0f}GiB budget forces {chosen.name} "
      f"({peak.total / 2 ** 30:.1f}GiB)")
EOF

    # 1F1B equivalence contract: bitwise vs the sequential oracle,
    # fp-tolerance vs no-pipeline; multi-group runs go through the
    # shared run_multidevice helper (forced host device count).
    python -m pytest -q tests/test_pipeline.py -x \
        -k "parity or bitwise or schedule_order or window"
}

obs_gate() {
    echo "== obs gate =="
    # DESIGN.md §14: (a) the disabled tracer path must cost <=2% on the
    # trimmed-mean step (trace-off vs trace-on, interleaved, trim=best
    # so one-sided load spikes on this box can't flake it), (b) the
    # exported trace must pass the minimal Chrome-trace schema checker,
    # and (c) Session.report() must produce a drift table covering
    # fwd/bwd/comm/io/opt with span-sourced measured values for BOTH
    # models. Explicit exit, not assert (PYTHONOPTIMIZE-safe).
    python - <<'EOF'
import dataclasses
import os
import sys
import tempfile

import jax

from repro import configs
from repro.api import RunConfig, compile as api_compile
from repro.obs import trace as trace_lib
from repro.obs.export import validate_chrome_trace
from benchmarks.common import interleaved_trimmed

cfg = dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                          input_width=16)
gb = 2
x, y = None, None
td = tempfile.mkdtemp()
trace_path = os.path.join(td, "trace.json")
s_off = api_compile(RunConfig(model=cfg, global_batch=gb))
s_on = api_compile(RunConfig(model=cfg, global_batch=gb, trace=trace_path))
x, y = s_off._synthetic_batch()
trace_lib.disable(s_on.tracer)  # recording scoped to the on cell only


def on_call():
    trace_lib.enable(s_on.tracer)
    try:
        jax.block_until_ready(s_on.step(x, y))
    finally:
        trace_lib.disable(s_on.tracer)


calls = {"off": lambda: jax.block_until_ready(s_off.step(x, y)),
         "on": on_call}
us = interleaved_trimmed(calls, rounds=20, trim="best", warmups=2)
over = (us["on"] - us["off"]) / us["off"]
if over > 0.02:
    sys.exit(f"obs gate: trace-on overhead {over * 100:+.2f}% > 2% "
             f"({us['on']:.0f}us vs {us['off']:.0f}us)")
print(f"obs gate: trace-on overhead {over * 100:+.2f}% (target <=2%)")
s_off.close()
s_on.close()  # flushes trace_path
ok, problems = validate_chrome_trace(trace_path)
if not ok:
    sys.exit("obs gate: exported trace failed schema check:\n  "
             + "\n  ".join(problems))
print(f"obs gate: exported trace valid ({trace_path})")

for model in ("cosmoflow-512", "unet3d-256"):
    mcfg = dataclasses.replace(configs.get_smoke_config(model),
                               input_width=16)
    s = api_compile(RunConfig(model=mcfg, global_batch=2))
    rep = s.report(reps=1)
    for phase in ("fwd", "bwd", "comm", "io", "opt"):
        try:
            row = rep.row(phase)
        except KeyError:
            sys.exit(f"obs gate: {mcfg.arch} drift table missing {phase}")
        if row.measured_s is None:
            sys.exit(f"obs gate: {mcfg.arch} drift {phase} has no "
                     f"span-sourced measurement: {row}")
        # fwd/io are direct span means (must be positive wall time);
        # bwd/comm/opt are cumulative-probe differences clamped at 0,
        # which noise on this box can legitimately zero out
        if phase in ("fwd", "io") and row.measured_s <= 0.0:
            sys.exit(f"obs gate: {mcfg.arch} drift {phase} span mean "
                     f"is not positive: {row}")
    if rep.source != "spans":
        sys.exit(f"obs gate: drift source {rep.source!r} != 'spans'")
    print(f"obs gate: {mcfg.arch} drift table covers fwd/bwd/comm/io/opt "
          f"({len(rep.flagged())} phases flagged on this backend)")
    s.close()
print("obs gate OK")
EOF

    # disabled-path + export + telemetry-stability unit contracts
    python -m pytest -q tests/test_obs.py -x
}

serve_gate() {
    echo "== serve gate =="
    # DESIGN.md §15: (a) the batched serving harness must hold >=1.3x
    # the unbatched oracle's throughput on the same requests (same
    # interleaved trim=best timing as the bench, so one-sided load
    # spikes on this box can't flake it), and (b) a traced serve
    # session's exported Chrome trace must pass the schema checker and
    # contain the four serve.* span names. Explicit exit, not assert
    # (PYTHONOPTIMIZE-safe).
    python - <<'EOF'
import json
import os
import sys
import tempfile

import jax
import numpy as np

from repro.api import RunConfig, compile as api_compile
from repro.configs.base import ConvNetConfig
from repro.obs.export import validate_chrome_trace
from benchmarks.common import interleaved_trimmed

cfg = ConvNetConfig(name="serve_gate8", family="conv3d", arch="cosmoflow",
                    input_width=8, in_channels=1, out_dim=4,
                    conv_channels=(2, 4), fc_dims=(16, 8))
n_req, max_batch = 96, 16
r = np.random.RandomState(0)
reqs = [r.randn(8, 8, 8, 1).astype(np.float32) for _ in range(n_req)]
sess = api_compile(RunConfig(model=cfg, mode="infer", global_batch=1))
h = sess.serve(max_batch=max_batch, max_wait_ms=5.0, max_queue=n_req)


def unbatched():
    for q in reqs:
        jax.block_until_ready(sess.predict(q[None]))


def batched():
    for f in h.submit_many(reqs):
        f.result(timeout=300)


us = interleaved_trimmed({"unbatched": unbatched, "batched": batched},
                         rounds=8, trim="best", warmups=1)
ratio = us["unbatched"] / us["batched"]
stats = h.stats()
h.close()
sess.close()
if stats["worker_failures"]:
    sys.exit(f"serve gate: {stats['worker_failures']:.0f} worker failures")
if ratio < 1.3:
    sys.exit(f"serve gate: batched harness only {ratio:.2f}x the "
             f"unbatched oracle ({us['batched'] / n_req:.0f}us vs "
             f"{us['unbatched'] / n_req:.0f}us per request; target "
             f">=1.3x at max_batch={max_batch})")
print(f"serve gate: batched {ratio:.2f}x unbatched "
      f"(fill {stats['mean_fill']:.1f}/{max_batch}; target >=1.3x)")

trace_path = os.path.join(tempfile.mkdtemp(), "serve_trace.json")
with api_compile(RunConfig(model=cfg, mode="infer",
                           trace=trace_path)) as ts:
    with ts.serve(max_batch=4, max_wait_ms=50.0) as th:
        for f in th.submit_many(reqs[:8]):
            f.result(timeout=300)
ok, problems = validate_chrome_trace(trace_path)
if not ok:
    sys.exit("serve gate: exported serve trace failed schema check:\n  "
             + "\n  ".join(problems))
names = {e.get("name")
         for e in json.load(open(trace_path))["traceEvents"]}
missing = [s for s in ("serve.enqueue", "serve.batch", "serve.forward",
                       "serve.reply") if s not in names]
if missing:
    sys.exit(f"serve gate: trace missing serve spans: {missing}")
print(f"serve gate: exported serve trace valid ({trace_path})")
print("serve gate OK")
EOF

    # checkpoint->inference parity + queue-semantics unit contracts
    python -m pytest -q tests/test_serve.py -x \
        -k "parity or cast_once or coalesces or backpressure or drain \
            or fault or idempotent or trace"
}

if [ "${1:-}" = "pipeline" ]; then
    pipeline_gate
    echo "verify: OK (pipeline only)"
    exit 0
fi
if [ "${1:-}" = "serve" ]; then
    serve_gate
    echo "verify: OK (serve only)"
    exit 0
fi
if [ "${1:-}" = "obs" ]; then
    obs_gate
    echo "verify: OK (obs only)"
    exit 0
fi

echo "== tier-1 pytest =="
python -m pytest -x -q

echo "== kernel perf smoke =="
if [ -n "${BENCH_OUT:-}" ]; then
    python -m benchmarks.run --quick --only kernels --json "$BENCH_OUT"
else
    python -m benchmarks.run --quick --only kernels
fi

echo "== grad-comm perf smoke =="
GC_JSON="$(mktemp /tmp/grad_comm_smoke.XXXXXX.json)"
python -m benchmarks.run --quick --only grad_comm --json "$GC_JSON"
python - "$GC_JSON" <<'EOF'
import json
import sys

rows = {r["name"]: r for r in json.load(open(sys.argv[1]))["rows"]}
mono = rows["grad_comm.micro.monolithic"]["us_per_call"]
ov = rows["grad_comm.micro.overlap"]["us_per_call"]
# regression gate: the overlapped lowering must not lose >10% to the
# monolithic tail psum on the reduction micro (it typically WINS >1.3x).
# explicit exit, not assert: asserts vanish under PYTHONOPTIMIZE.
if ov > 1.10 * mono:
    sys.exit(f"grad-comm overlap regressed: {ov:.0f}us vs monolithic "
             f"{mono:.0f}us ({mono / ov:.2f}x)")
print(f"grad-comm smoke OK: overlap {mono / ov:.2f}x vs monolithic")
EOF
rm -f "$GC_JSON"

echo "== plan gate =="
# DESIGN.md §5: the planner's chosen CosmoFlow plan must price <= the
# fixed-degree plan in the perf model, at the paper's strong-scaling
# operating point. Explicit exit, not assert (PYTHONOPTIMIZE-safe).
python - <<'EOF'
import sys

from repro import configs
from repro.core import plan as plan_lib
from repro.core.perf_model import V100

cfg = configs.get_config("cosmoflow-512")
kw = dict(spatial_degree=16, data_degree=16, global_batch=64)
chosen = plan_lib.plan_convnet(cfg, V100, **kw)
# independently-constructed baseline (NOT drawn from the planner's
# candidate set): the legacy fixed-degree plan, priced the same way
fixed, fixed_cost = plan_lib.price_fixed_degree(cfg, V100, **kw)
if chosen.cost > fixed_cost:
    sys.exit(f"plan gate: chosen {chosen.name} ({chosen.cost * 1e3:.2f}ms) "
             f"prices above fixed-degree {fixed.name} "
             f"({fixed_cost * 1e3:.2f}ms)")
print(f"plan gate OK: {chosen.name} {chosen.cost * 1e3:.2f}ms <= "
      f"{fixed.name} {fixed_cost * 1e3:.2f}ms "
      f"({fixed_cost / chosen.cost:.3f}x)")
EOF

# planned-vs-fixed e2e parity (the reshard equivalence contract)
python -m pytest -q tests/test_plan.py -k "parity" -x

echo "== memory gate =="
# DESIGN.md §9: with a budget below the pure-data-parallel peak for
# 256^3 CosmoFlow, the budgeted planner must return a plan whose
# MODELED peak fits the budget (the paper's capacity argument; no real
# OOM involved). Explicit exit, not assert (PYTHONOPTIMIZE-safe).
python - <<'EOF'
import sys

from repro import configs
from repro.core import memory, plan as plan_lib
from repro.core.perf_model import V100

cfg = configs.get_config("cosmoflow-256")
gb = 4
dp = memory.data_parallel_peak_bytes(cfg, global_batch=gb, num_gpus=4)
budget = 0.5 * dp.total
chosen = plan_lib.plan_convnet(
    cfg, V100, spatial_degree=1, data_degree=4, global_batch=gb,
    memory_budget_bytes=budget, spatial_options=(1, 2, 4, 8),
    precisions=("fp32", "bf16"))
peak = memory.plan_peak_bytes(cfg, chosen, global_batch=gb)
if peak.total > budget:
    sys.exit(f"memory gate: chosen {chosen.name} peaks at "
             f"{peak.total / 2 ** 30:.2f}GiB over the "
             f"{budget / 2 ** 30:.2f}GiB budget")
print(f"memory gate OK: {chosen.name} {peak.total / 2 ** 30:.2f}GiB <= "
      f"budget {budget / 2 ** 30:.2f}GiB "
      f"(pure-DP {dp.total / 2 ** 30:.2f}GiB would not fit)")
EOF

# remat equivalence (the §9 recompute contract) + model-vs-measured 15%
python -m pytest -q tests/test_memory.py -x \
    -k "remat_grad_parity or within_15pct"

echo "== api gate =="
# DESIGN.md §10: a budgeted Session must (a) report a modeled peak that
# fits the configured budget and (b) carry exactly the plan the §5
# planner argmins for the same inputs — i.e. compile() adds policy, not
# improvisation. Explicit exit, not assert (PYTHONOPTIMIZE-safe).
python - <<'EOF'
import dataclasses
import sys

from repro import configs
from repro.api import RunConfig, compile as api_compile
from repro.core import memory, plan as plan_lib
from repro.core.perf_model import V100

cfg = dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                          input_width=16)
gb = 2
dp = memory.data_parallel_peak_bytes(cfg, global_batch=gb, num_gpus=1)
budget = 1.05 * dp.total  # feasible, but tight enough to exercise the path
sess = api_compile(RunConfig(model=cfg, global_batch=gb,
                             memory_budget_gib=budget / 2 ** 30))
rep = sess.describe()
if rep.modeled_peak.total > budget:
    sys.exit(f"api gate: Session peak {rep.modeled_peak.total / 2 ** 20:.2f}"
             f"MiB over the {budget / 2 ** 20:.2f}MiB budget")
chosen = plan_lib.plan_convnet(
    cfg, V100, spatial_degree=1, data_degree=1, global_batch=gb,
    grad_comm="overlap", memory_budget_bytes=budget,
    precisions=("fp32", "bf16"), spatial_options=(1,))
if rep.plan_name != chosen.name:
    sys.exit(f"api gate: Session plan {rep.plan_name!r} != planner argmin "
             f"{chosen.name!r}")
print(f"api gate OK: {rep.plan_name} peak "
      f"{rep.modeled_peak.total / 2 ** 20:.2f}MiB <= budget "
      f"{budget / 2 ** 20:.2f}MiB")
EOF

# the quickstart example end-to-end (the README path: one compile call)
python examples/quickstart.py --steps 3

echo "== resilience gate =="
# DESIGN.md §11: (a) a kill-and-auto-resume run must reproduce the
# uninterrupted run's loss trajectory and final params BITWISE, and
# (b) the guarded step must not cost more than 10% over unguarded on
# this noisy CPU box (the bench target is <=2%; the gate is looser so
# scheduler jitter can't flake it). Explicit exit (PYTHONOPTIMIZE-safe).
python - <<'EOF'
import dataclasses
import sys
import tempfile
import time

import jax
import numpy as np

from repro import configs
from repro.api import RunConfig, compile as api_compile, supervisor
from repro.core import faults

cfg = dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                          input_width=16)
base = RunConfig(model=cfg, global_batch=2, total_steps=20)

ref = supervisor.run(dataclasses.replace(
    base, checkpoint_dir=tempfile.mkdtemp()), 6, save_every=2)
with faults.active(faults.FaultSpec("device.loss", at_steps=(4,),
                                    max_fires=1)):
    got = supervisor.run(dataclasses.replace(
        base, checkpoint_dir=tempfile.mkdtemp()), 6, save_every=2)
if got.restarts != 1 or got.resumes != 1:
    sys.exit(f"resilience gate: expected 1 restart/1 resume, got "
             f"{got.restarts}/{got.resumes}: {got.events}")
if got.losses != ref.losses:
    sys.exit(f"resilience gate: resumed trajectory not bitwise:\n"
             f"  ref {ref.losses}\n  got {got.losses}")
for a, b in zip(jax.tree.leaves(ref.session.params),
                jax.tree.leaves(got.session.params)):
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        sys.exit("resilience gate: resumed params not bitwise")
print(f"resilience gate OK: kill-and-resume bitwise "
      f"(recovery {got.recovery_s[0]:.2f}s)")

# guard overhead smoke: interleaved medians, 10% CPU-noise gate
x, y = ref.session._synthetic_batch()
sessions = {g: api_compile(dataclasses.replace(base, guard=g))
            for g in (False, True)}
for s in sessions.values():
    s.step((x, y)); jax.block_until_ready(s.step((x, y)))
samples = {g: [] for g in sessions}
for _ in range(20):
    for g, s in sessions.items():
        t0 = time.perf_counter()
        jax.block_until_ready(s.step((x, y)))
        samples[g].append(time.perf_counter() - t0)
med = {g: sorted(v)[len(v) // 2] for g, v in samples.items()}
over = (med[True] - med[False]) / med[False]
if over > 0.10:
    sys.exit(f"resilience gate: guard overhead {over * 100:+.1f}% > 10% "
             f"({med[True] * 1e3:.2f}ms vs {med[False] * 1e3:.2f}ms)")
print(f"resilience gate OK: guard overhead {over * 100:+.1f}% "
      f"(target <=2%, gate <=10%)")
EOF

# crash-safety + guarded-step unit contracts
python -m pytest -q tests/test_resilience.py -x \
    -k "crash_mid_save or corruption or guard_skips"

echo "== io gate =="
# DESIGN.md §12: (a) prefetch-vs-sync batch sequences must be BITWISE
# identical for the same seed (the sync loader is the equivalence
# oracle), (b) on a bandwidth-throttled store the prefetch loader's
# samples/sec must be >= the sync loader's (the overlap win; the bench
# target is >=1.2x, the gate asserts parity-or-better so scheduler
# jitter can't flake it), and (c) a persistent loader.read fault firing
# inside the prefetch worker must fail the consumer's step loudly as
# StoreReadError. Explicit exit, not assert (PYTHONOPTIMIZE-safe).
python - <<'EOF'
import dataclasses
import sys
import tempfile
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import compat, faults
from repro.data import pipeline, prefetch, store, synthetic
from repro.data.store import StoreReadError
from repro.models import cosmoflow
from repro.optim.adam import Adam, constant
from repro.train.train_step import make_convnet_train_step

cfg = dataclasses.replace(configs.get_smoke_config("cosmoflow-512"),
                          input_width=16)
gb, steps = 2, 6
d = tempfile.mkdtemp()
cubes, targets = synthetic.make_cosmology_dataset(
    8, cfg.input_width, channels=cfg.in_channels, seed=0)
store.write_dataset(d, cubes, targets)
mesh = compat.make_mesh((1, 1), ("data", "model"))
spec = P("data", "model", None, None, None)
bpe = 8 // gb


def loader(pf, throttle=None, cache=True):
    ld = pipeline.SpatialParallelLoader(
        store.HyperslabStore(d, throttle_mbps=throttle), mesh, spec,
        global_batch=gb, seed=0, cache=cache)
    return prefetch.PrefetchLoader(ld, depth=2) if pf else ld


# (a) bitwise parity over two shuffled epochs
sync, pf = loader(False), loader(True)
for t in range(2 * bpe):
    e, b = divmod(t, bpe)
    o1, o2 = sync.schedule_for_epoch(e), pf.schedule_for_epoch(e)
    if not np.array_equal(o1, o2):
        sys.exit(f"io gate: schedules diverge at epoch {e}")
    xs, ys = sync.load_batch(o1[b * gb:(b + 1) * gb])
    xp, yp = pf.load_batch(o2[b * gb:(b + 1) * gb])
    if not (np.array_equal(np.asarray(xs), np.asarray(xp))
            and np.array_equal(np.asarray(ys), np.asarray(yp))):
        sys.exit(f"io gate: batch {t} not bitwise sync-vs-prefetch")
sync.close(); pf.close()
print("io gate: prefetch-vs-sync batches bitwise over 2 epochs")

# (b) throttled mini-e2e: prefetch samples/sec >= sync
opt = Adam(lr=constant(1e-3))
step = jax.jit(make_convnet_train_step(cfg, mesh, opt, global_batch=gb,
                                       jit=False))
p0 = cosmoflow.init_params(jax.random.PRNGKey(0), cfg)
st0 = opt.init(p0)
warm = loader(False)
xw, yw = warm.load_batch(np.arange(gb)); warm.close()
p, st, _ = step(p0, st0, xw, yw, np.int32(0))
jax.block_until_ready(step(p, st, xw, yw, np.int32(0))[2])
total = {}
for kind in (False, True):
    ld = loader(kind, throttle=2.0, cache=False)
    p, st = p0, st0
    t0 = time.perf_counter()
    for t in range(steps):
        e, b = divmod(t, bpe)
        order = ld.schedule_for_epoch(e)
        x, y = ld.load_batch(order[b * gb:(b + 1) * gb])
        p, st, loss = step(p, st, x, y, np.int32(t))
        jax.block_until_ready(loss)
    total[kind] = time.perf_counter() - t0
    ld.close()
if total[True] > total[False]:
    sys.exit(f"io gate: prefetch slower than sync on the throttled store "
             f"({total[True]:.2f}s vs {total[False]:.2f}s)")
print(f"io gate: prefetch {total[False] / total[True]:.2f}x vs sync "
      f"(throttled store; bench target >=1.2x)")

# (c) persistent worker-thread fault -> StoreReadError on the consumer
pf = loader(True, cache=False)
with faults.active(faults.FaultSpec("loader.read", probability=1.0)):
    order = pf.epoch_schedule()
    try:
        pf.load_batch(order[:gb])
    except StoreReadError as e:
        print(f"io gate: worker fault surfaced loudly: {e}")
    else:
        sys.exit("io gate: persistent loader.read fault did NOT surface "
                 "as StoreReadError on the consumer")
pf.close()
print("io gate OK")
EOF

# determinism + supervisor loader-mode bitwise resume unit contracts
python -m pytest -q tests/test_io_pipeline.py -x \
    -k "bitwise or deterministic or surfaces_on_consumer"

pipeline_gate

obs_gate

serve_gate

echo "verify: OK"
