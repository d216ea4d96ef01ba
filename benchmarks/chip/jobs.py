"""The general drivers that turn a traffic file into work on the system
under test, one per ``kind`` of traffic:

- ``train``: a closed training loop through ``repro.api.compile`` and
  ``Session.step``, fed by ``Session.make_loader`` over a store of
  seeded volumes that set-up writes (see ``TrainData``). Set-up drives
  the session through its first ``checked_steps`` steps, the ones the
  reference follows; the window then drives the same session on.

Each driver returns the end-to-end readings, the context the per-layer
readers work from, and the numbers compared with the reference. What
the program is given comes from the seed alone: the weights, the
volumes and their order.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from benchmarks.chip import check, flops, harness, reference, xplane

TRACE_STEPS = 6


def model_config(cfg: dict):
    """The program's model description, built from the file's sizes."""
    from repro.configs.base import ConvNetConfig

    m = cfg["model"]
    return ConvNetConfig(
        name=cfg["name"], family="conv3d", arch=m["arch"],
        input_width=m["input_width"], in_channels=m["in_channels"],
        out_dim=m["out_dim"], conv_channels=tuple(m["conv_channels"]),
        kernel_size=m["kernel_size"], fc_dims=tuple(m["fc_dims"]),
        batchnorm=m["batchnorm"])


def run_config(cfg: dict, **kw):
    from repro.api import RunConfig

    opt = cfg["optimizer"]
    return RunConfig(model=model_config(cfg), data=cfg["layout"]["data"],
                     spatial=cfg["layout"]["spatial"], lr=opt["lr"],
                     lr_schedule=opt["lr_schedule"],
                     total_steps=opt["total_steps"],
                     precision=cfg["precision"]["program"], **kw)


def place_weights(cfg: dict, seed: int, like):
    """The seed's weights, made on the device in one jitted call and laid
    out as the session's own (``like``)."""
    import jax

    shardings = jax.tree.map(lambda a: a.sharding, like)
    init = jax.jit(lambda k: reference.init_params(k, cfg["model"]),
                   out_shardings=shardings)
    return init(jax.random.PRNGKey(seed))


def volumes(rng, n: int, m: dict):
    w, c = m["input_width"], m["in_channels"]
    return [rng.standard_normal((w, w, w, c), dtype=np.float32)
            for _ in range(n)]


def _act_sharding(cfg: dict, devices):
    """For the reference over several chips: shard a block's output on
    depth while each chip keeps at least 4 planes."""
    n = len(devices)
    if n == 1:
        return None, None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("d",))
    depth = NamedSharding(mesh, P(None, "d"))

    def constrain(h):
        if h.shape[1] % n == 0 and h.shape[1] // n >= 4:
            return jax.lax.with_sharding_constraint(h, depth)
        return h

    return constrain, depth


@contextlib.contextmanager
def traced(trace_dir: str):
    """The JAX profiler on, with the program's own host spans recorded
    and anchored to the profiler's clock."""
    import jax
    from repro.obs import trace as trace_lib

    tracer = trace_lib.Tracer()
    prev = trace_lib.active()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = True
    rec = {"tracer": tracer}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.ANCHOR):
            rec["anchor_ns"] = time.perf_counter_ns()
        trace_lib.enable(tracer)
        yield rec
    finally:
        trace_lib.disable(tracer)
        if prev is not None:
            trace_lib.enable(prev)
        jax.profiler.stop_trace()


def _trace_context(rec: dict, trace_dir: str, n_devices: int,
                   keep: Optional[str]) -> dict:
    spans = [(e.name, e.ts_ns + rec["tracer"].epoch_ns, e.dur_ns)
             for e in rec["tracer"].events() if e.dur_ns is not None]
    if keep:
        shutil.copytree(trace_dir, keep, dirs_exist_ok=True)
        with open(os.path.join(keep, "host.json"), "w") as f:
            json.dump({"devices": n_devices, "spans": spans,
                       "anchor_ns": rec["anchor_ns"],
                       "window_ns": rec["window"]}, f)
    return xplane.reduce(xplane.find_xplane(trace_dir), n_devices,
                         spans, rec["anchor_ns"], rec["window"])


# ------------------------------------------------------------- training --
class TrainData:
    """The seed's volumes and targets, written as the loader's store.

    The store's epoch holds ``epoch_samples`` samples, as a training set
    does, so no window reaches its end; their contents are ``volumes``
    distinct seeded volumes, each file a hard link to one of them, so
    set-up writes only those. ``link`` gives the epoch's first samples,
    in the loader's order, distinct contents, so the checked steps see
    rows that all differ."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.data import store

        m = cfg["model"]
        n = traffic["epoch_samples"]
        self.seed_weights, seed_data, self.seed_order = harness.sub_seeds(
            seed, 3)
        rng = np.random.default_rng(seed_data)
        self.cubes = volumes(rng, traffic["volumes"], m)
        self.targets = rng.standard_normal((n, m["out_dim"]),
                                           dtype=np.float32)
        self.content: Optional[np.ndarray] = None
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench-store-")
        self.root = self._tmp.name
        store.write_dataset(self.root, self.cubes, self.targets)
        for j in range(len(self.cubes)):
            os.rename(self._file(j), self._file(j, "v"))
        index = os.path.join(self.root, "index.json")
        meta = harness.load_json(index)
        with open(index, "w") as f:
            json.dump(dict(meta, num_samples=n), f)

    def _file(self, i: int, what: str = "x") -> str:
        return os.path.join(self.root, f"{what}_{i:06d}.npy")

    def link(self, order) -> None:
        """Give the sample at position p of the epoch's ``order`` the
        contents of volume p mod ``volumes``."""
        if self.content is not None:
            return
        content = np.empty(len(order), dtype=np.int64)
        content[np.asarray(order)] = np.arange(len(order)) % len(self.cubes)
        for i, j in enumerate(content):
            os.link(self._file(int(j), "v"), self._file(i))
        self.content = content

    def cube(self, i) -> np.ndarray:
        return self.cubes[int(self.content[int(i)])]

    def close(self) -> None:
        self._tmp.cleanup()


def train_program(cfg: dict, traffic: dict, data: TrainData,
                  fault: Optional[Callable] = None):
    """Build the session and its loader, and drive them through the
    checked steps. Returns (session, loader, batch iterator, the ids of
    the checked batches, the program's readings)."""
    import jax
    import jax.numpy as jnp
    from repro.api import compile as compile_run

    gb = traffic["global_batch"]
    session = compile_run(run_config(cfg, global_batch=gb))
    if fault is not None:
        fault(session)
    session.params = place_weights(cfg, data.seed_weights, session.params)
    data.link(session.make_loader(data.root, seed=data.seed_order,
                                  prefetch=0).schedule_for_epoch(0))
    # no host cache: a training set's epoch does not fit in host memory
    loader = session.make_loader(data.root, seed=data.seed_order,
                                 prefetch=traffic["prefetch"], cache=False)

    def batch_ids():
        epoch = 0
        while True:
            order = loader.schedule_for_epoch(epoch)
            for b in range(len(order) // gb):
                yield order[b * gb:(b + 1) * gb]
            epoch += 1

    ids = batch_ids()
    p0 = jax.tree.map(jnp.copy, session.params)
    checked, prog = [], {"losses": []}
    for t in range(traffic["checked_steps"]):
        checked.append(next(ids))
        loss = session.step(loader.load_batch(checked[-1]))
        prog["losses"].append(float(loss))
        if t == 0:
            # Adam's first moment after one step is (1 - beta1) g
            prog["grad_norms"] = {
                k: float(v) / 0.1 for k, v in jax.jit(reference.leaf_norms)(
                    session.opt_state.m).items()}
    prog["change_norms"] = {k: float(v) for k, v in jax.jit(
        lambda a, b: reference.leaf_norms(
            {k: a[k] - b[k] for k in a}))(session.params, p0).items()}
    return session, loader, ids, checked, prog


def train_reference(cfg: dict, data: TrainData, checked, devices) -> dict:
    """The reference's readings over the checked batches, run once the
    program's state is gone; over several chips its activations are
    sharded on depth."""
    import jax
    import jax.numpy as jnp

    m = cfg["model"]
    constrain, batch_sharding = _act_sharding(cfg, devices)
    put = ((lambda a: jax.device_put(a, batch_sharding)) if batch_sharding
           else jnp.asarray)
    batches = [(put(np.stack([data.cube(i) for i in b])),
                jnp.asarray(data.targets[np.asarray(b)])) for b in checked]
    params0 = jax.jit(lambda k: reference.init_params(k, m))(
        jax.random.PRNGKey(data.seed_weights))
    return reference.train_readings(params0, batches, m, cfg["optimizer"],
                                    act_sharding=constrain)


def train(cfg: dict, traffic: dict, seed: int, seconds: float,
          trace: bool, t_process: float, devices, compiles,
          log: Callable = print, fault: Optional[Callable] = None,
          keep_trace: Optional[str] = None) -> dict:
    """One run of a training cell. ``fault``, for the tests, may break
    the session before the checked steps."""
    m = cfg["model"]
    gb = traffic["global_batch"]
    data = TrainData(cfg, traffic, seed)
    session, loader, ids, checked, prog = train_program(
        cfg, traffic, data, fault)
    setup_s = time.perf_counter() - t_process

    c0 = len(compiles)
    stall0 = session.telemetry().get("io_stall_s", 0.0)
    steps, prev = 0, None
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        loss = session.step(loader.load_batch(next(ids)))
        if prev is not None:
            prev.block_until_ready()   # at most two steps in flight
        prev, steps = loss, steps + 1
    final_loss = float(prev)
    window_s = time.perf_counter() - t0
    window_compiles = len(compiles) - c0
    stall_s = session.telemetry().get("io_stall_s", 0.0) - stall0
    peak = harness.memory_peak_bytes(devices)
    log(f"window: {steps} steps of {gb} in {window_s:.4f} s; compiles in "
        f"the window: {window_compiles}; last loss {final_loss}")

    ctx = {"chips": len(devices), "train": True,
           "global_batch": gb, "steps": steps, "window_s": window_s,
           "io_stall_s": stall_s, "train_flops": flops.train_flops(m),
           "conv_flops": flops.conv_flops(m, True),
           "conv_bytes": flops.conv_bytes(m, True)}
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as td:
            with traced(td) as rec:
                t_a = time.perf_counter_ns()
                for _ in range(TRACE_STEPS):
                    loss = session.step(loader.load_batch(next(ids)))
                    prev.block_until_ready()
                    prev = loss
                prev.block_until_ready()
                rec["window"] = (t_a, time.perf_counter_ns())
            ctx["trace"] = _trace_context(rec, td, len(devices),
                                            keep_trace)
            ctx["trace_steps"] = TRACE_STEPS
    session.close()
    del session, loader, prev, loss
    gc.collect()

    t_ref = time.perf_counter()
    ref = train_reference(cfg, data, checked, devices)
    data.close()
    log(f"reference: {time.perf_counter() - t_ref:.2f} s; losses "
        f"{ref['losses']} against the program's {prog['losses']}")
    e2e = {"samples_per_s": steps * gb / window_s,
           "hbm_peak_gib": None if peak is None else peak / 2 ** 30,
           "setup_s": setup_s}
    return {"e2e": e2e, "ctx": ctx, "numbers": check.train_numbers(prog, ref),
            "attempted": steps, "failed": 0 if math.isfinite(final_loss)
            else 1, "memory_peak_bytes": peak,
            "window_compiles": window_compiles}


DRIVERS = {"train": train}
