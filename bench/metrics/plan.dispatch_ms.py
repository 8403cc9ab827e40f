"""Mean host duration of the engine's ``plan.dispatch`` span in the
window (``repro.obs``).  The call is asynchronous: this is the enqueue,
not the device's work."""


def read(ctx):
    s = ctx.spans.get("plan.dispatch")
    return s["mean_ms"] if s and s["count"] else None
