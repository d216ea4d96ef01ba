"""Device time of max-pooling, forward and backward, in ms per traced
step: ``scope_s["pool"]`` of ``attribution.reduce``. The whole
``scope_s`` table, and the share of device op time no scope owns, are
logged."""


def read(ctx):
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not ctx["train"] or not scopes or "pool" not in scopes:
        return None
    total = sum(scopes.values())
    ctx["log"]("scope_s: " + ", ".join(
        f"{k} {1e3 * v / ctx['trace_steps']:.3f} ms/step"
        for k, v in sorted(scopes.items(), key=lambda t: -t[1])))
    ctx["log"](f"unscoped share of device op time: "
               f"{100.0 * scopes.get('unscoped', 0.0) / total:.2f}%")
    return 1e3 * scopes["pool"] / ctx["trace_steps"]
