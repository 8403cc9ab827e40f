"""Mean host duration of the batcher's ``batch.slot_wait`` span in the
window (``repro.obs``): after a dispatch, the wait for a free slot in
the completion queue, the server's backpressure on the batcher."""


def read(ctx):
    s = ctx.spans.get("batch.slot_wait")
    return s["mean_ms"] if s and s["count"] else None
