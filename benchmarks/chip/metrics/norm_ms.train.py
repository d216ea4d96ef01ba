"""Device time of batch-norm and its leaky-ReLU, forward and backward,
in ms per traced step: ``scope_s["norm"]`` of ``attribution.reduce``."""


def read(ctx):
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not ctx["train"] or not scopes or "norm" not in scopes:
        return None
    return 1e3 * scopes["norm"] / ctx["trace_steps"]
