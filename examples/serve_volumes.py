"""Serve 3D volumes through the batched inference harness (DESIGN.md
§15): build a forward-only ``InferenceSession`` — fresh, or restored
straight from a training checkpoint — and push a stream of requests
through ``serve()``, printing throughput against the unbatched oracle
and the enqueue->reply latency quantiles.

    PYTHONPATH=src python examples/serve_volumes.py
    PYTHONPATH=src python examples/serve_volumes.py --arch unet3d-256
    PYTHONPATH=src python examples/serve_volumes.py --ckpt out/ck \
        --model 2 --max-batch 16

``--model N`` shards each volume's forward over N spatially-parallel
devices (the paper's capacity argument applied to serving: a volume
that OOMs one device fits the group; ``describe()`` prices the drop).
"""
import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.api import RunConfig, compile
from repro.api import cli
from repro.configs.base import ConvNetConfig
from repro.serve import InferenceSession

# the default demo model: small enough that per-call dispatch dominates
# the forward, so request coalescing visibly wins on a CPU box (the
# verify.sh serve gate's regime). The --arch smoke presets are
# compute-bound on CPU — there batching pays off on accelerators, while
# spatial sharding (--model N) is what cuts per-device memory anywhere.
_TINY = ConvNetConfig(name="serve_demo8", family="conv3d",
                      arch="cosmoflow", input_width=8, in_channels=1,
                      out_dim=4, conv_channels=(2, 4), fc_dims=(16, 8))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny8",
                    choices=("tiny8", "cosmoflow-512", "unet3d-256"))
    ap.add_argument("--ckpt", default=None,
                    help="restore params from a training checkpoint "
                         "instead of serving a fresh init")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel serving degree")
    ap.add_argument("--model", type=int, default=1,
                    help="spatial-parallel serving degree")
    ap.add_argument("--precision", default=None,
                    choices=("fp32", "bf16", "fp16"),
                    help="serving precision (masters cast once at load)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome/Perfetto trace of the serve "
                         "spans to PATH")
    cli.add_serve_args(ap)
    args = ap.parse_args()
    cli.use_compile_cache()

    if args.ckpt:
        sess = InferenceSession.restore(
            args.ckpt, data=args.data, spatial=args.model,
            precision=args.precision, trace=args.trace)
    else:
        cfg = (_TINY if args.arch == "tiny8"
               else configs.get_smoke_config(args.arch))
        over = {"precision": args.precision} if args.precision else {}
        if args.trace:
            over["trace"] = args.trace
        sess = compile(RunConfig(model=cfg, mode="infer",
                                 global_batch=args.data,
                                 data=args.data, spatial=args.model,
                                 **over))
    print(sess.describe())

    cfg = sess.cfg
    w = cfg.input_width
    r = np.random.RandomState(0)
    reqs = [r.randn(w, w, w, cfg.in_channels).astype(np.float32)
            for _ in range(args.requests)]

    # absorb jit compiles for both shapes the run will use (a live
    # server pays these once per batch size, on first encounter)
    sess.predict(np.stack(reqs[:1]))
    if len(reqs) >= args.max_batch:
        sess.predict(np.stack(reqs[:args.max_batch]))

    # unbatched oracle: one forward per request, each reply awaited
    # before the next (what a caller without the harness would do)
    t0 = time.perf_counter()
    for q in reqs:
        jax.block_until_ready(sess.predict(q[None]))
    un_s = time.perf_counter() - t0

    # the batched harness on the same requests
    with sess.serve(**cli.harness_kwargs(args)) as h:
        t0 = time.perf_counter()
        futs = h.submit_many(reqs)
        rows = [f.result(timeout=600) for f in futs]
        b_s = time.perf_counter() - t0
    tele = sess.telemetry()
    print(f"unbatched: {args.requests / un_s:7.1f} req/s")
    print(f"batched:   {args.requests / b_s:7.1f} req/s "
          f"({un_s / b_s:.2f}x; mean fill "
          f"{tele['serve.batch_fill']:.1f}/{args.max_batch})")
    print(f"latency ms: p50 {tele['serve.latency_p50_ms']:.2f}  "
          f"p95 {tele['serve.latency_p95_ms']:.2f}  "
          f"p99 {tele['serve.latency_p99_ms']:.2f}")
    print(f"first reply: shape {rows[0].shape}, dtype {rows[0].dtype}")
    sess.close()
    if args.trace:
        print(f"trace written to {args.trace} (open at ui.perfetto.dev)")


if __name__ == "__main__":
    main()
