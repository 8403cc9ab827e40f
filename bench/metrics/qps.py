"""Query rows answered in the window, over the window's seconds.

Rows count when their request completes.  The request completing next
after the window's close counts for the share of its service that fell
inside the window (its rows times the time from the last completion in
the window to the close, over the time between the two completions), so
the rate does not move in steps of one whole micro-batch.
"""


def read(ctx):
    done = sorted((res.completed_at, r.rows) for r in ctx.records
                  for res in [r.outcome()]
                  if res is not None and res.error is None
                  and res.completed_at >= ctx.t0)
    inside = [(t, n) for t, n in done if t <= ctx.t1]
    rows = float(sum(n for _, n in inside))
    after = [(t, n) for t, n in done if t > ctx.t1]
    if inside and after:
        last, (nxt, n) = inside[-1][0], after[0]
        rows += n * (ctx.t1 - last) / (nxt - last)
    return rows / ctx.seconds
