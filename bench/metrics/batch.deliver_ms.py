"""Mean host duration of the completer's ``batch.deliver`` span in the
window (``repro.obs``): a finished batch's rows scattered to its
requests, each request counted and settled, its callbacks run."""


def read(ctx):
    s = ctx.spans.get("batch.deliver")
    return s["mean_ms"] if s and s["count"] else None
