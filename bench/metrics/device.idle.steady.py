"""Device idle share of the traced window in the steady cells: one minus
the union of the intervals in which an operation ran on the device,
over the window."""

from _idle import idle_pct


def read(ctx):
    return idle_pct(ctx)
