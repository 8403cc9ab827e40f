"""Latency percentiles over every request due in the window."""

import math


def percentile_ms(ctx, q: float):
    """Nearest-rank ``q``-th percentile of due-to-completion latency, in
    ms, over all requests due in the window.  A request that failed or
    never completed counts as infinitely late; it enters as the time it
    was waited for, a lower bound, so the number stays finite."""
    lat = []
    for r in ctx.records:
        x = r.latency_s()
        lat.append(ctx.gave_up - r.due if x == math.inf else x)
    if not lat:
        return None
    lat.sort()
    return 1e3 * lat[max(0, math.ceil(q / 100.0 * len(lat)) - 1)]
