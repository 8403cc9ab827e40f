"""Rows per dispatched micro-batch over the window (``ServerStats``
counters ``batched_rows`` / ``batches``)."""


def read(ctx):
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    rows = ctx.stats1["batched_rows"] - ctx.stats0["batched_rows"]
    return rows / batches if batches else None
