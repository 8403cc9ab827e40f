"""Milliseconds of garbage collection per second of window: the total
of the ``host.gc`` spans (``repro.obs``, one per collection, on
whichever thread collected) that started in the window, over its
seconds.  0.0 where the program recorded its delivery spans and no
collection ran; ``None`` from a program that records neither, which
has no collection hook either."""


def read(ctx):
    if "batch.deliver" not in ctx.spans:
        return None
    s = ctx.spans.get("host.gc")
    return (s["total_ms"] if s else 0.0) / ctx.seconds
