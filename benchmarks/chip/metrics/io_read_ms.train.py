"""Host time the loader spent in store reads, in ms per batch: the
``io.read`` spans of the traced stretch over its ``io.load`` spans
(``attribution.input_spans``). The reads' rate is logged."""

from benchmarks.chip import attribution


def read(ctx):
    spans = ctx.get("host_spans")
    if not ctx["train"] or spans is None:
        return None
    io = attribution.input_spans(spans)
    if not io["batches"] or not io["read_s"]:
        return None
    ctx["log"](f"io_read_ms.train: {io['read_bytes']} bytes in "
               f"{io['read_s']:.6f} s, "
               f"{io['read_bytes'] / io['read_s'] / 1e9:.3f} GB/s")
    return 1e3 * io["read_s"] / io["batches"]
