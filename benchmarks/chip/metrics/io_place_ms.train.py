"""Host time the loader spent placing batches on the devices, in ms per
batch: the ``io.place`` spans of the traced stretch over its ``io.load``
spans (``attribution.input_spans``). The placement's rate, and
``io.load``'s self time (host stacking and the loader's own Python) per
batch, are logged."""

from benchmarks.chip import attribution


def read(ctx):
    spans = ctx.get("host_spans")
    if not ctx["train"] or spans is None:
        return None
    io = attribution.input_spans(spans)
    if not io["batches"] or not io["place_s"]:
        return None
    ctx["log"](f"io_place_ms.train: {io['place_bytes']} bytes in "
               f"{io['place_s']:.6f} s, "
               f"{io['place_bytes'] / io['place_s'] / 1e9:.3f} GB/s; "
               f"io.load self time {1e3 * io['self_s'] / io['batches']:.3f} "
               f"ms a batch")
    return 1e3 * io["place_s"] / io["batches"]
