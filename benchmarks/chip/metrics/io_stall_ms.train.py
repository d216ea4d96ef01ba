"""Host time a training step waited on the prefetch queue, in ms per
step of the untraced window (``Session.telemetry()["io_stall_s"]``)."""


def read(ctx):
    if not ctx["train"] or not ctx["steps"]:
        return None
    return 1e3 * ctx["io_stall_s"] / ctx["steps"]
