"""Device time lost to input, in ms per traced step: the device-idle
seconds of the traced stretch during which the step thread was inside
an input span (``io.wait`` on the prefetch queue, or a synchronous
``io.load`` with its ``io.read`` and ``io.place``), from
``attribution.reduce``'s ``idle_under_s``, over the traced steps."""


def read(ctx):
    under = (ctx.get("trace") or {}).get("idle_under_s")
    if not ctx["train"] or under is None:
        return None
    io_s = sum(v for k, v in under.items() if k.startswith("io."))
    ctx["log"](f"io_exposed_ms.train: device idle by step-thread span "
               f"{under}")
    return 1e3 * io_s / ctx["trace_steps"]
