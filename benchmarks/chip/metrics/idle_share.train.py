"""Share of the traced stretch of training in which no operation ran on
a device, in percent, averaged over the cell's chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not ctx["train"] or tr is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
