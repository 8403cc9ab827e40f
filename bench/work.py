"""The least time a chip could take for some work.

The work itself is counted by each configuration's kind
(``bench/kinds/<kind>.py: work(cfg, rows)``), from the configuration's
shapes: what the search *needs*, not what today's code does, so the
count stays the same whichever implementation runs.
"""

from __future__ import annotations


def least_time(work: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for
    ``work`` (``{"ops", "bytes", "peak"}``, ``peak`` naming the compute
    peak of ``peaks`` it runs at), the larger of operations over that
    peak and bytes over HBM bandwidth, and which of the two binds
    (``compute`` or ``hbm``)."""
    t_ops = work["ops"] / peaks[work["peak"]]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "hbm")
