"""Batches still in flight (dispatched, not yet delivered) ahead of each
batch dispatched in the window: ``ServerStats`` counters
``inflight_ahead`` / ``batches`` over the window.  ``None`` from a
program that does not count them."""


def read(ctx):
    if "inflight_ahead" not in ctx.stats1:
        return None
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    ahead = ctx.stats1["inflight_ahead"] - ctx.stats0["inflight_ahead"]
    return ahead / batches if batches else None
