"""99th percentile of due-to-completion latency over all requests due
in the window."""

from _latency import percentile_ms


def read(ctx):
    return percentile_ms(ctx, 99)
