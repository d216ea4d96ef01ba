"""Observability subsystem (DESIGN.md §14): tracer spans and threads,
the near-free disabled path, Chrome-trace export + schema validation,
metrics registry + the telemetry-key stability contract, drift-table
semantics, and the instrumented seams (train step, prefetch worker,
checkpoint publish, 1F1B dispatcher threads)."""
import dataclasses
import json
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.api import RunConfig, Session
from repro.api import compile as api_compile
from repro.obs import export as export_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.obs import report as report_lib
from repro.models import cosmoflow

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the chip benchmark's reading of a scope, checked here on compiled steps
from benchmarks.chip.attribution import scope_of  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with no process-active tracer."""
    trace_lib.disable()
    yield
    trace_lib.disable()


def _smoke(model="cosmoflow-512", width=16):
    return dataclasses.replace(configs.get_smoke_config(model),
                               input_width=width)


# ------------------------------------------------------------- tracer ----
def test_tracer_spans_threads_and_aggregates():
    tr = trace_lib.Tracer()
    trace_lib.enable(tr)
    with trace_lib.span("outer", k=1):
        with trace_lib.span("inner"):
            pass
    trace_lib.instant("mark", v=2)
    trace_lib.count("hits", 3)

    def worker():
        with trace_lib.span("inner"):
            pass

    t = threading.Thread(target=worker, name="obs-test-worker")
    t.start(); t.join()
    names = [e.name for e in tr.events()]
    assert names.count("inner") == 2 and "outer" in names and "mark" in names
    threads = {e.thread for e in tr.events() if e.name == "inner"}
    assert "obs-test-worker" in threads and len(threads) == 2
    agg = tr.span_seconds()
    assert agg["inner"][0] == 2 and agg["inner"][1] >= 0.0
    # the outer span strictly contains the first inner span
    outer = next(e for e in tr.events() if e.name == "outer")
    inner = next(e for e in tr.events() if e.name == "inner")
    assert outer.ts_ns <= inner.ts_ns
    assert outer.ts_ns + outer.dur_ns >= inner.ts_ns + inner.dur_ns
    assert tr.metrics.counter("hits").value == 3


def test_disabled_path_is_null_singleton_and_records_nothing():
    tr = trace_lib.Tracer()
    assert trace_lib.active() is None
    s = trace_lib.span("anything", k=1)
    assert s is trace_lib.NULL_SPAN  # the cached no-op, not a new object
    with s:
        pass
    trace_lib.instant("nothing")
    trace_lib.count("nothing")
    assert len(tr) == 0
    trace_lib.enable(tr)
    assert trace_lib.span("real") is not trace_lib.NULL_SPAN


def test_disable_is_owner_guarded():
    a, b = trace_lib.Tracer(), trace_lib.Tracer()
    trace_lib.enable(a)
    trace_lib.disable(b)  # not the active tracer: must be a no-op
    assert trace_lib.active() is a
    trace_lib.disable(a)
    assert trace_lib.active() is None


def test_tracer_caps_events_and_counts_drops():
    tr = trace_lib.Tracer(max_events=3)
    trace_lib.enable(tr)
    for i in range(5):
        trace_lib.instant(f"e{i}")
    assert len(tr) == 3 and tr.dropped == 2


# ------------------------------------------------------------ metrics ----
def test_metrics_instruments():
    reg = metrics_lib.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for v in (1.0, 3.0):
        reg.histogram("h").observe(v)
    assert reg.counter("c").value == 5
    assert reg.gauge("g").value == 2.5
    h = reg.histogram("h")
    assert (h.count, h.total, h.min, h.max, h.mean) == (2, 4.0, 1.0, 3.0, 2.0)
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["g"] == 2.5 and snap["h.mean"] == 2.0


def test_metrics_absorb_is_bitwise_identity():
    """The §14 telemetry migration contract: routing a dict through the
    registry's gauges returns the same keys, in order, with the same
    values AND types (ints stay ints)."""
    reg = metrics_lib.MetricsRegistry()
    src = {"steps": 3.0, "skipped_steps": 2, "loss_scale": 65536.0,
           "io_pfs_bytes": 1048576.0}
    out = reg.absorb(src)
    assert list(out) == list(src)
    for k in src:
        assert type(out[k]) is type(src[k]) and out[k] == src[k]
    assert reg.gauge("skipped_steps").value == 2


def test_metrics_jsonl_sink(tmp_path):
    p = tmp_path / "m.jsonl"
    sink = metrics_lib.MetricsJsonlSink(str(p))
    sink.write({"step": 0, "wall_s": 0.25})
    sink.write({"step": 1, "wall_s": 0.5})
    sink.close()
    sink.close()  # idempotent
    rows = [json.loads(line) for line in p.read_text().splitlines()]
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[1]["wall_s"] == 0.5


# ------------------------------------------------------------- export ----
def test_chrome_export_structure(tmp_path):
    tr = trace_lib.Tracer()
    trace_lib.enable(tr)
    with trace_lib.span("phase.work", step=1):
        pass
    trace_lib.instant("phase.mark")

    def worker():
        with trace_lib.span("phase.work"):
            pass

    t = threading.Thread(target=worker, name="io-prefetch_0")
    t.start(); t.join()
    path = tmp_path / "t.json"
    export_lib.write_chrome_trace(str(path), tr)
    doc = json.loads(path.read_text())
    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= {"io-prefetch_0"}
    xs = [e for e in ev if e["ph"] == "X"]
    assert len(xs) == 2 and all(e["dur"] >= 0 for e in xs)
    assert {e["tid"] for e in xs} == {m["tid"] for m in meta}
    inst = next(e for e in ev if e["ph"] == "i")
    assert inst["s"] == "t" and inst["name"] == "phase.mark"
    assert all(e["cat"] == "phase" for e in xs)
    ok, problems = export_lib.validate_chrome_trace(str(path))
    assert ok and problems == []


@pytest.mark.parametrize("doc,frag", [
    ([], "traceEvents"),                                   # not an object
    ({"traceEvents": {}}, "traceEvents"),                  # not a list
    ({"traceEvents": [{"ph": "X", "pid": 1, "tid": 1, "ts": 0}]},
     "name"),                                              # missing name
    ({"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 1,
                       "ts": 0}]}, "dur"),                 # X without dur
    ({"traceEvents": [{"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": 1}]}, "args.name"),          # bare metadata
])
def test_validator_rejects(tmp_path, doc, frag):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(doc))
    ok, problems = export_lib.validate_chrome_trace(str(p))
    assert not ok
    assert any(frag in pr for pr in problems)


# -------------------------------------------------------------- drift ----
def test_drift_ratio_and_flag_semantics():
    rep = report_lib.drift(
        modeled={"fwd": 1.0, "bwd": 1.0, "comm": 1.0, "io": 1.0},
        measured={"fwd": 2.5, "bwd": 0.3, "comm": 1.5, "step": 4.0},
        flag_ratio=2.0)
    assert rep.row("fwd").flagged and rep.row("fwd").ratio == 2.5
    assert rep.row("bwd").flagged          # 0.3 < 1/2: slow-side drift
    assert not rep.row("comm").flagged     # 1.5x within the band
    # single-sided rows carry no ratio and are never flagged
    assert rep.row("io").ratio is None and not rep.row("io").flagged
    assert rep.row("step").ratio is None and not rep.row("step").flagged
    assert rep.phases()[: 4] == ("fwd", "bwd", "comm", "io")
    js = rep.to_json()
    assert js["source"] == "spans" and len(js["rows"]) == len(rep.rows)
    assert "drift" in str(rep)


def test_modeled_phases_cover_the_table():
    cfg = _smoke()
    from repro.core import plan as plan_lib
    from repro.core.perf_model import V100
    plan = plan_lib.uniform_plan(cfg)
    phases = report_lib.modeled_phases(cfg, V100, plan, global_batch=2,
                                       grad_comm="overlap")
    assert set(phases) == {"fwd", "bwd", "comm", "io", "opt", "step"}
    assert all(v >= 0.0 for v in phases.values())
    assert phases["step"] > 0.0 and phases["opt"] > 0.0


# ---------------------------------------------------- bench row schema ----
def test_bench_row_schema():
    from benchmarks.common import validate_rows
    good = [{"name": "a", "us_per_call": 1.0, "derived": "x",
             "trace_path": None},
            {"name": "b", "us_per_call": 2, "derived": "",
             "trace_path": "/tmp/t.json"}]
    validate_rows(good)  # must not raise
    for bad, frag in (
            ([{"name": "a", "us_per_call": 1.0, "derived": "x"}], "keys"),
            ([{"name": "", "us_per_call": 1.0, "derived": "x",
               "trace_path": None}], "name"),
            ([{"name": "a", "us_per_call": "1", "derived": "x",
               "trace_path": None}], "us_per_call"),
            ([{"name": "a", "us_per_call": 1.0, "derived": "x",
               "trace_path": ""}], "trace_path")):
        with pytest.raises(ValueError, match=frag):
            validate_rows(bad)


# ------------------------------------------------------------ session ----
def test_session_trace_export_and_idempotent_close(tmp_path):
    path = str(tmp_path / "trace.json")
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                 trace=path))
    x, y = sess._synthetic_batch()
    for _ in range(2):
        sess.step((x, y))
    assert trace_lib.active() is sess.tracer
    sess.close()
    sess.close()  # idempotent: no double export, no error
    assert trace_lib.active() is None
    ok, problems = export_lib.validate_chrome_trace(path)
    assert ok, problems
    ev = json.loads(open(path).read())["traceEvents"]
    steps = [e for e in ev if e["name"] == "train.step"]
    assert len(steps) == 2
    assert [e["args"]["step"] for e in steps] == [0, 1]


def test_session_metrics_jsonl_rows(tmp_path):
    p = tmp_path / "metrics.jsonl"
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                 metrics_jsonl=str(p)))
    x, y = sess._synthetic_batch()
    for _ in range(3):
        sess.step((x, y))
    sess.close()
    rows = [json.loads(line) for line in p.read_text().splitlines()]
    assert [r["step"] for r in rows] == [0, 1, 2]
    assert all(r["wall_s"] > 0 for r in rows)


def test_export_trace_uniquifies_foreign_files(tmp_path):
    """A pre-existing file this session did not write is never clobbered
    (the supervisor-restart contract); re-exports by the same session
    overwrite their own earlier file."""
    path = tmp_path / "trace.json"
    path.write_text("{}")  # a foreign file
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                 trace=True))
    x, y = sess._synthetic_batch()
    sess.step((x, y))
    out = sess.export_trace(str(path))
    assert out == str(tmp_path / "trace-1.json")
    assert path.read_text() == "{}"
    assert sess.export_trace(out) == out  # own file: overwrite in place
    sess.close()


def test_untraced_session_step_records_nothing():
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2))
    x, y = sess._synthetic_batch()
    sess.step((x, y))
    assert trace_lib.active() is None and len(sess.tracer) == 0
    sess.close()


# ------------------------------------------- telemetry-key stability ----
def _capture_absorb(monkeypatch):
    cap = {}
    orig = metrics_lib.MetricsRegistry.absorb

    def absorb(self, values):
        cap["in"] = dict(values)
        out = orig(self, values)
        cap["out"] = dict(out)
        return out

    monkeypatch.setattr(metrics_lib.MetricsRegistry, "absorb", absorb)
    return cap


_TELEMETRY_KEYS = ("steps", "skipped_steps", "loss_scale",
                   "loader_retries", "resumes", "pool_index_share")
_IO_KEYS = ("io_pfs_bytes", "io_cache_hit_ratio", "io_in_place_share",
            "io_stall_s", "io_queue_occupancy")


def test_telemetry_survives_registry_migration_bitwise(monkeypatch):
    """spatial=1, pipeline off, with a prefetching loader: the full §11
    + §12 key set (with ``pool_index_share``) passes through the
    MetricsRegistry unchanged — same keys, same order, same values, same
    types."""
    cap = _capture_absorb(monkeypatch)
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2,
                                 guard=True))
    loader = sess.make_loader(num_samples=4, prefetch=1)
    order = loader.schedule_for_epoch(0)
    x, y = loader.load_batch(order[:2])
    sess.step((x, y))
    tel = sess.telemetry()
    assert set(tel) == set(_TELEMETRY_KEYS) | set(_IO_KEYS)
    assert list(cap["in"]) == list(cap["out"]) == list(tel)
    for k in cap["in"]:
        assert type(cap["out"][k]) is type(cap["in"][k])
        assert cap["out"][k] == cap["in"][k]
    assert isinstance(tel["skipped_steps"], int)
    sess.close()


_TELEMETRY_CELL_SCRIPT = """
import dataclasses
import jax
from repro import configs
from repro.api import RunConfig, compile as api_compile
import repro.obs.metrics as metrics_lib

cap = {{}}
orig = metrics_lib.MetricsRegistry.absorb
def absorb(self, values):
    cap['in'] = dict(values)
    out = orig(self, values)
    cap['out'] = dict(out)
    return out
metrics_lib.MetricsRegistry.absorb = absorb

cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          input_width=16)
sess = api_compile(RunConfig(model=cfg, global_batch=4, guard={guard},
                             **{kw}))
x, y = sess._synthetic_batch()
sess.step((x, y))
tel = sess.telemetry()
expect = {{'steps', 'skipped_steps', 'loss_scale', 'loader_retries',
           'resumes', 'pool_index_share'}}
assert set(tel) == expect, sorted(tel)
assert tel['pool_index_share'] == 1.0, tel
assert list(cap['in']) == list(cap['out']) == list(tel)
for k in cap['in']:
    assert type(cap['out'][k]) is type(cap['in'][k]), k
    assert cap['out'][k] == cap['in'][k], k
assert isinstance(tel['skipped_steps'], int)
sess.close()
print('TELEMETRY-OK', sorted(tel))
"""


@pytest.mark.parametrize("kw,guard", [
    (dict(data=2, spatial=2), True),
    (dict(pipeline=2, data=2, micro_batches=2), False),
])
def test_telemetry_stability_hybrid_cells(multidevice, kw, guard):
    """The same migration contract at spatial=2 and at pipeline=2 (the
    guard has no cross-group lowering, so the pipelined cell runs
    unguarded — matching what compile() supports there)."""
    out = multidevice(_TELEMETRY_CELL_SCRIPT.format(kw=kw, guard=guard),
                      devices=4)
    assert "TELEMETRY-OK" in out


# ------------------------------------------------- instrumented seams ----
def test_prefetch_worker_and_wait_spans():
    tr = trace_lib.Tracer()
    trace_lib.enable(tr)
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2))
    loader = sess.make_loader(num_samples=4, prefetch=1)
    order = loader.schedule_for_epoch(0)
    for b in range(2):
        jax.block_until_ready(loader.load_batch(order[b * 2:(b + 1) * 2]))
    sess.close()
    spans = [e for e in tr.events() if e.name == "io.load"]
    assert spans and all(e.thread.startswith("io-prefetch") for e in spans)
    assert all(e.attrs["samples"] == 2 for e in spans)
    assert any(e.name == "io.wait" for e in tr.events())


# ------------------------------------------------- named scopes (jit) ----
def _instructions(hlo_text):
    """(opcode, op_name or None) of each instruction but parameters."""
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(", line)
        if m and m.group(1) != "parameter":
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else None))
    return out


def _step_hlo(**kw):
    cfg = _smoke()
    sess = api_compile(RunConfig(model=cfg, global_batch=2, **kw))
    w = cfg.input_width
    x = jnp.ones((2, w, w, w, cfg.in_channels), jnp.float32)
    y = jnp.ones((2, cfg.out_dim), jnp.float32)
    text = sess._step_fn.lower(sess.params, sess.opt_state, x, y,
                               jnp.asarray(0, jnp.int32)).compile().as_text()
    sess.close()
    return text


def test_step_ops_carry_layer_scopes():
    """The compiled step's convolutions sit under ``conv`` and its
    max-pooling, forward and backward, under ``pool``, each inside its
    ``block{i}``; backward ops inherit the scope through the name stack,
    the pool's custom backward included. The pools take the saved-index
    path, so the step holds no select-and-scatter: inside a block only
    that path emits strided slices and ``gt`` compares (forward) and
    ``eq`` compares (backward), and each carries ``pool``. (The CPU
    compiler drops the metadata of the weight-gradient convolutions it
    rewrites, so only instructions that carry an ``op_name`` are
    checked.)"""
    insts = _instructions(_step_hlo())
    convs = [n for op, n in insts if op == "convolution" and n]
    assert len(convs) >= len(_smoke().conv_channels)
    named = [n for _, n in insts if n and n.endswith("/conv_general_dilated")]
    assert named
    for n in convs + named:
        assert scope_of(n) == "conv", n
    assert not any(op == "select-and-scatter" for op, _ in insts)
    index_path = {
        "fwd": [n for op, n in insts if n and "/jvp(block" in n
                and (op, n.split("/")[-1]) in (("slice", "slice"),
                                               ("compare", "gt"))],
        "bwd": [n for op, n in insts if n and "transpose(jvp(block" in n
                and (op, n.split("/")[-1]) == ("compare", "eq")]}
    for way, names in index_path.items():
        for b in range(cosmoflow.num_pools(_smoke())):
            assert any(f"jvp(block{b})" in n for n in names), (way, b)
        for n in names:
            assert scope_of(n) == "pool", (way, n)
    pooled = [n for _, n in insts if n and "/pool/" in n]
    assert all(scope_of(n) == "pool" for n in pooled), pooled
    assert any(n and n.split("/")[1] == "optimizer" for _, n in insts)


@pytest.mark.parametrize("width,share", [(16, 1.0), (13, 0.0)])
def test_pool_index_share(width, share):
    """``pool_index_share`` reads the lowering the step has: 1.0 when
    every pool takes the saved-index path (even widths, no
    select-and-scatter), 0 when the one pool of a 13^3 input falls back
    to ``reduce_window`` and autodiff's select-and-scatter."""
    cfg = _smoke(width=width)
    sess = api_compile(RunConfig(model=cfg, global_batch=2))
    x, y = sess._synthetic_batch()
    lowered = sess._step_fn.lower(sess.params, sess.opt_state, x, y,
                                  jnp.asarray(0, jnp.int32)).as_text()
    assert ("select_and_scatter" in lowered) == (share == 0.0)
    assert sess.telemetry()["pool_index_share"] == share
    sess.step((x, y))
    assert sess.telemetry()["pool_index_share"] == share
    sess.close()


def test_halo_exchange_ops_carry_halo(multidevice):
    """At spatial=4 every collective-permute of the step is a halo
    exchange and says so."""
    out = multidevice(f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
from test_obs import _instructions, _step_hlo, scope_of
perms = [n for op, n in _instructions(_step_hlo(spatial=4))
         if op.startswith("collective-permute")]
assert perms and all(n and scope_of(n) == "halo" for n in perms), perms
print("HALO-SCOPED", len(perms))
""", devices=4)
    assert "HALO-SCOPED" in out


def test_checkpoint_spans(tmp_path):
    from repro.train import checkpoint
    tr = trace_lib.Tracer()
    trace_lib.enable(tr)
    tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    d = str(tmp_path / "ck")
    checkpoint.save(d, tree, step=3)
    checkpoint.restore(d, tree)
    names = [e.name for e in tr.events()]
    assert names.count("ckpt.save") == 1
    assert names.count("ckpt.publish") == 1
    assert names.count("ckpt.restore") == 1
    save = next(e for e in tr.events() if e.name == "ckpt.save")
    pub = next(e for e in tr.events() if e.name == "ckpt.publish")
    assert save.attrs["step"] == 3
    # publish nests inside save (the atomic-rename tail of the write)
    assert save.ts_ns <= pub.ts_ns
    assert save.ts_ns + save.dur_ns >= pub.ts_ns + pub.dur_ns


def test_report_measured_phases_come_from_spans():
    sess = api_compile(RunConfig(model=_smoke(), global_batch=2))
    rep = sess.report(reps=1)
    for phase in ("fwd", "bwd", "comm", "io", "opt", "step"):
        assert rep.row(phase).measured_s is not None, phase
    assert rep.row("fwd").measured_s > 0 and rep.row("io").measured_s > 0
    assert rep.source == "spans"
    # the measured column is the span aggregate, not a probe return dict
    agg = sess.tracer.span_seconds()
    assert rep.row("fwd").measured_s == agg["probe.fwd"][1]
    # report() only borrowed the tracer: the session stays untraced
    assert trace_lib.active() is None
    sess.close()


_PIPELINE_TRACE_SCRIPT = """
import dataclasses
import json
import jax
from repro import configs
from repro.api import RunConfig, compile as api_compile

trace = {trace!r}
cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          input_width=16)
sess = api_compile(RunConfig(model=cfg, global_batch=4, pipeline=2,
                             data=2, micro_batches=2, trace=trace))
x, y = sess._synthetic_batch()
for _ in range(2):
    sess.step((x, y))
sess.close()

from repro.obs.export import validate_chrome_trace
ok, problems = validate_chrome_trace(trace)
assert ok, problems
ev = json.load(open(trace))['traceEvents']
tracks = {{e['args']['name'] for e in ev if e['ph'] == 'M'}}
disp = sorted(t for t in tracks if t.startswith('pipe-dispatch'))
assert len(disp) >= 2, tracks  # one track per group dispatcher thread
by = {{}}
for e in ev:
    if e['ph'] == 'X':
        by.setdefault(e['name'], []).append(e)
# per-node 1F1B work spans, tagged with group/micro for bubble reading:
# early stages run split F / B halves, the last stage fused FB
for name in ('pipe.F', 'pipe.B', 'pipe.FB'):
    assert name in by, sorted(by)
work = by['pipe.F'] + by['pipe.B'] + by['pipe.FB']
assert {{s['args']['group'] for s in work}} == {{0, 1}}
assert {{s['args']['micro'] for s in work}} == {{0, 1}}
# warmup fill then steady 1F1B: group 0's first F precedes its first B
f0 = min(s['ts'] for s in by['pipe.F'] if s['args']['group'] == 0)
b0 = min(s['ts'] for s in by['pipe.B'] if s['args']['group'] == 0)
assert f0 < b0
assert 'pipe.place' in by and 'pipe.update' in by
print('PIPETRACE-OK', len(ev), disp)
"""


def test_pipeline_1f1b_trace_has_dispatcher_tracks(multidevice, tmp_path):
    trace = str(tmp_path / "pipe_trace.json")
    out = multidevice(_PIPELINE_TRACE_SCRIPT.format(trace=trace),
                      devices=4)
    assert "PIPETRACE-OK" in out


_SUPERVISOR_TRACE_SCRIPT = """
import dataclasses
import glob
import json
import os
from repro import configs
from repro.api import RunConfig, supervisor
from repro.core import faults
from repro.obs.export import validate_chrome_trace

root = {root!r}
trace = os.path.join(root, 'trace.json')
cfg = dataclasses.replace(configs.get_smoke_config('cosmoflow-512'),
                          input_width=16)
base = RunConfig(model=cfg, global_batch=2,
                 checkpoint_dir=os.path.join(root, 'ck'), trace=trace)
with faults.active(faults.FaultSpec('device.loss', at_steps=(2,),
                                    max_fires=1)):
    rep = supervisor.run(base, 4, save_every=2)
rep.session.close()
files = sorted(glob.glob(os.path.join(root, 'trace*.json')))
assert len(files) == 2, files  # one trace PER session, not interleaved
for f in files:
    ok, problems = validate_chrome_trace(f)
    assert ok, (f, problems)
msgs = {{f: [e['args']['msg']
             for e in json.load(open(f))['traceEvents']
             if e['name'] == 'supervisor.event'] for f in files}}
# the dying session's trace carries its failure; the restarted session's
# trace starts clean at its own resume (no interleaving either way)
died = [f for f, m in msgs.items() if any('failure' in s for s in m)]
resumed = [f for f, m in msgs.items() if any('resumed' in s for s in m)]
assert len(died) == 1 and len(resumed) == 1, msgs
assert died[0] != resumed[0], msgs
assert not any('failure' in s for s in msgs[resumed[0]]), msgs
print('SUPTRACE-OK', sorted(len(m) for m in msgs.values()))
"""


def test_supervisor_restart_writes_separate_traces(tmp_path):
    """Satellite (a): Session.close() on restart disables + flushes the
    dying session's tracer, so a supervised run yields one trace file
    per session instead of interleaving both into one."""
    from tests.conftest import run_multidevice
    out = run_multidevice(
        _SUPERVISOR_TRACE_SCRIPT.format(root=str(tmp_path)), devices=1)
    assert "SUPTRACE-OK" in out
