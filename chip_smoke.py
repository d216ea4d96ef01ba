#!/usr/bin/env python3
"""Smoke run of the main path on TPU: CosmoFlow-128 at its published
widths (128^3 x 4 input, conv channels 16..256, FC 2048-256-4), random
weights from a seed, through the user entry points only.

    python chip_smoke.py            # one chip: train, then serve
    python chip_smoke.py --chips 4  # four chips: spatial=4 vs spatial=1

One chip: ``compile(RunConfig(model="cosmoflow-128", global_batch=4))``
trains 5 steps from a seeded synthetic store and evaluates once; the
checkpoint it saves is restored by ``InferenceSession.restore`` and 8
requests are served through ``serve(max_batch=4)``. Four chips: the same
5 steps at ``spatial=4`` and at ``spatial=1``, from the same seed and
batches, in this one process, at the default precision and at "highest";
the loss trajectories must agree and every chip must hold part of the
activations.

The printed times are those of a smoke run, not benchmark numbers. The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises, and a host without a TPU exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "cosmoflow-128"
GLOBAL_BATCH = 4
STEPS = 5
REQUESTS = 8
# serving a batch through the harness and through predict() runs the
# same compiled forward on the same rows: DESIGN.md §15's tolerance
SERVE_TOL = 1e-6
SPATIAL = 4
# spatial=SPATIAL vs spatial=1, relative loss difference. At the chip's
# default precision the first losses, on identical params, differ by
# 4.4e-4 (TPU v5e), and the chaotic first Adam steps grow that to 9.7e-2
# by step 5, so only the first step is compared there. A lost or
# misrouted halo moves the first loss by 9.4e-3 or 1.4e-1 (four CPU
# devices, smoke size).
FIRST_STEP_REL_TOL = 1e-3
# At "highest" precision only the reduction order of the BN statistics
# and the loss differs: 1.2e-6 over STEPS steps on TPU v5e, 7.3e-6 on
# four CPU devices at smoke size.
SPATIAL_REL_TOL = 2e-5

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_log: list = []


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _watch_compiles() -> None:
    """Record the duration of every XLA compile in this process (once)."""
    import jax

    if getattr(_watch_compiles, "done", False):
        return

    def listener(event, duration, **_):
        if event == _COMPILE_EVENT:
            _compile_log.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    _watch_compiles.done = True


def _memory_stats(device) -> dict:
    """The device's allocator counters (empty where the backend has
    none, as on CPU)."""
    return dict(sorted((device.memory_stats() or {}).items()))


def _run_steps(session):
    """``STEPS`` steps over the session's seeded synthetic store: losses,
    per-step seconds, compiles after the first step, and the last batch."""
    import jax
    import numpy as np

    gb = session.config.global_batch
    loader = session.make_loader(num_samples=2 * gb, seed=0)
    per_epoch = len(loader.schedule_for_epoch(0)) // gb
    losses, secs, compiles_after_warmup = [], [], 0
    batch = None
    for t in range(STEPS):
        order = loader.schedule_for_epoch(t // per_epoch)
        b = t % per_epoch
        batch = loader.load_batch(order[b * gb:(b + 1) * gb])
        n0 = len(_compile_log)
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(session.step(batch)))
        secs.append(time.perf_counter() - t0)
        if t:
            compiles_after_warmup += len(_compile_log) - n0
        losses.append(loss)
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return losses, secs, compiles_after_warmup, batch


def train_phase(model, ckpt_dir: str, *, log=print) -> dict:
    """Train ``STEPS`` steps of ``model`` (a registry name or a
    ``ConvNetConfig``) at ``GLOBAL_BATCH``, evaluate once, and save the
    run to ``ckpt_dir``."""
    import jax
    import numpy as np

    from repro.api import RunConfig, compile

    _watch_compiles()
    t0 = time.perf_counter()
    session = compile(RunConfig(model=model, global_batch=GLOBAL_BATCH))
    build_s = time.perf_counter() - t0
    with session:
        before = {k: np.asarray(v) for k, v in session.params.items()}
        losses, secs, compiles, (x, y) = _run_steps(session)
        skipped = session.telemetry()["skipped_steps"]
        _check(skipped == 0, f"{skipped} guarded steps were skipped")
        changed = sum(not np.array_equal(before[k], np.asarray(v))
                      for k, v in session.params.items())
        _check(changed == len(before),
               f"only {changed} of {len(before)} params changed")
        _check(compiles == 0,
               f"{compiles} compiles after the warm-up step")
        eval_loss, preds = session.evaluate(x, y)
        eval_loss = float(eval_loss)
        _check(np.isfinite(eval_loss), f"non-finite eval loss {eval_loss}")
        _check(bool(np.all(np.isfinite(np.asarray(preds)))),
               "non-finite eval predictions")
        session.save(ckpt_dir)
    out = {"build_s": build_s, "first_step_s": secs[0],
           "median_step_s": statistics.median(secs[1:]),
           "losses": losses, "eval_loss": eval_loss,
           "compiles_after_warmup": compiles,
           "memory_stats": _memory_stats(jax.devices()[0])}
    log(f"train {session.cfg.name}: global_batch={GLOBAL_BATCH} "
        f"params={session.cfg.param_count()}")
    log(f"  compile (session build + first step): "
        f"{build_s + secs[0]:.2f} s")
    log(f"  median step after warm-up: {out['median_step_s']:.4f} s "
        f"over {len(secs) - 1} steps; compiles after warm-up: {compiles}")
    log(f"  losses: {losses}  eval loss: {eval_loss}")
    log(f"  memory_stats device 0: {out['memory_stats']}")
    return out


def serve_phase(ckpt_dir: str, *, log=print) -> dict:
    """Restore ``ckpt_dir`` for inference and serve ``REQUESTS`` seeded
    volumes through the batched harness; each answer must match
    ``predict`` on the same batch."""
    import numpy as np

    from repro.serve import InferenceSession

    sess = InferenceSession.restore(ckpt_dir, global_batch=GLOBAL_BATCH)
    w, c = sess.cfg.input_width, sess.cfg.in_channels
    rng = np.random.default_rng(0)
    vols = rng.standard_normal((REQUESTS, w, w, w, c), dtype=np.float32)
    with sess:
        t0 = time.perf_counter()
        # a generous fill window: batches are then exactly consecutive
        # groups of max_batch requests, which predict() below repeats
        with sess.serve(max_batch=GLOBAL_BATCH, max_wait_ms=30_000) as h:
            futures = h.submit_many(list(vols))
            outs = [f.result(timeout=600) for f in futures]
            stats = h.stats()
        serve_s = time.perf_counter() - t0
        _check(stats["worker_failures"] == 0,
               f"{stats['worker_failures']} serving batches failed")
        _check(stats["batches"] == REQUESTS // GLOBAL_BATCH,
               f"requests coalesced into {stats['batches']} batches, "
               f"not {REQUESTS // GLOBAL_BATCH}")
        for i, o in enumerate(outs):
            _check(o.shape == (sess.cfg.out_dim,) and
                   bool(np.all(np.isfinite(o))),
                   f"request {i}: bad output {o!r}")
        served = np.stack(outs)
        direct = np.concatenate([
            np.asarray(sess.predict(vols[i:i + GLOBAL_BATCH]))
            for i in range(0, REQUESTS, GLOBAL_BATCH)])
        diff = float(np.max(np.abs(served - direct)))
        _check(np.allclose(served, direct, rtol=SERVE_TOL, atol=SERVE_TOL),
               f"served vs predict max |diff| {diff}")
    out = {"requests": len(outs), "batches": stats["batches"],
           "serve_s": serve_s, "max_abs_diff": diff}
    log(f"serve: {len(outs)} requests in {stats['batches']:.0f} batches "
        f"in {serve_s:.2f} s (compile included); served vs predict max "
        f"|diff| {diff}")
    return out


def spatial_phase(model, *, log=print) -> dict:
    """The same ``STEPS`` steps at ``SPATIAL`` and at 1 from one seed and
    one batch order, at the default precision and then at "highest" (see
    ``FIRST_STEP_REL_TOL``). The default-precision runs go first, so each
    device's peak counters after the ``SPATIAL`` run are that run's own,
    and device 0's after the spatial=1 run are the one-chip step's."""
    import jax
    import numpy as np

    from repro.api import RunConfig, compile

    _watch_compiles()
    runs, mem = {}, {}
    for prec in (None, "highest"):
        for s in (SPATIAL, 1):
            with jax.default_matmul_precision(prec), compile(RunConfig(
                    model=model, global_batch=GLOBAL_BATCH,
                    spatial=s)) as session:
                losses, secs, compiles, _ = _run_steps(session)
            runs[prec, s] = np.asarray(losses)
            log(f"spatial={s} precision={prec or 'default'}: losses "
                f"{losses}; first step {secs[0]:.2f} s, median after "
                f"warm-up {statistics.median(secs[1:]):.4f} s, compiles "
                f"after warm-up {compiles}")
            mem.setdefault(s, [_memory_stats(d)
                               for d in jax.devices()[:s]])

    def rel(prec):
        a, b = runs[prec, SPATIAL], runs[prec, 1]
        return np.abs(a - b) / np.abs(b)

    first = float(rel(None)[0])
    worst = float(np.max(rel("highest")))
    log(f"spatial={SPATIAL} vs 1: first-step relative loss difference at "
        f"the default precision {first} (tolerance {FIRST_STEP_REL_TOL}); "
        f"max over {STEPS} steps at highest {worst} (tolerance "
        f"{SPATIAL_REL_TOL})")
    for i, m in enumerate(mem[SPATIAL]):
        log(f"memory_stats device {i} after spatial={SPATIAL}: {m}")
    log(f"memory_stats device 0 after spatial=1: {mem[1][0]}")
    _check(first <= FIRST_STEP_REL_TOL,
           f"first-step losses differ by {first} > {FIRST_STEP_REL_TOL}")
    _check(worst <= SPATIAL_REL_TOL,
           f"loss trajectories differ by {worst} > {SPATIAL_REL_TOL}")
    # the step's activations and temporaries live in the reservation;
    # peak_bytes_in_use holds only what outlives a program (params, Adam
    # state, a batch shard), the same on every layout
    one = mem[1][0].get("peak_bytes_reserved")
    spread = [m.get("peak_bytes_reserved") for m in mem[SPATIAL]]
    if one is not None:
        _check(max(spread) <= 0.5 * one and min(spread) >= 0.5 * max(spread),
               f"step memory not spread over the {SPATIAL} devices: "
               f"reserved {spread} against {one} on one")
    return {"losses": runs, "first_step_rel_diff": first,
            "max_rel_diff": worst, "peak_bytes_reserved": spread}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the spatial=4 vs spatial=1 phase")
    args = ap.parse_args(argv)

    from repro.api import cli

    cli.use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 1
    print(f"smoke run, not a benchmark: {MODEL} on {len(devices)} x "
          f"{dev.device_kind}")
    if args.chips == 4:
        spatial_phase(MODEL)
    else:
        with tempfile.TemporaryDirectory() as tmp:
            ckpt = os.path.join(tmp, "ckpt")
            train_phase(MODEL, ckpt)
            serve_phase(ckpt)
    print(f"compiles in this process: {len(_compile_log)}, "
          f"{sum(_compile_log):.2f} s in total")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
