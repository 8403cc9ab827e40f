"""The search executable's share of its roofline: the least time the
chip could take for one micro-batch (the kind's ``work`` and the peaks
table) over its device time per call from the trace.  A micro-batch
holds the window's mean rows per dispatched batch (``ServerStats``):
in the bulk mix every request is one full micro-batch."""

from work import least_time


def read(ctx):
    if ctx.trace is None:
        return None
    calls, seconds = ctx.trace.module_time("chunk_fn")
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    rows = ctx.stats1["batched_rows"] - ctx.stats0["batched_rows"]
    if not calls or not seconds or not batches:
        return None
    least, _bound = least_time(ctx.kind.work(ctx.cfg, rows / batches),
                               ctx.peaks)
    return 100.0 * least / (seconds / calls)
