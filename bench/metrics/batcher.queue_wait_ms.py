"""Mean time a window request waited in the batcher: from submit to the
dispatch of its micro-batch (``SearchResult`` timestamps)."""


def read(ctx):
    waits = []
    for r in ctx.records:
        res = r.outcome()
        if res is not None and res.dispatched_at:
            waits.append(res.dispatched_at - res.submitted_at)
    return 1e3 * sum(waits) / len(waits) if waits else None
