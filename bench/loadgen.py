"""The general load generator: one reader of every traffic file.

A traffic mix is a JSON file of parameters under ``bench/traffic/``;
this module is the only code that reads one.  Two loops:

* ``closed``: ``clients`` threads, each sending a ``rows``-row request
  and waiting for its reply before sending the next.  Batch callers.
* ``open``: one thread submits ``rows``-row requests at times fixed in
  advance and never waits for a reply.  Independent users.  Arrivals
  are ``poisson``: ``round(rate_per_s * seconds)`` arrivals placed as
  sorted uniform draws over the window.  That is a Poisson process
  conditioned on its count, so every seed brings the same number of
  requests, in a different order.

Each request is timed from when it was *due* (closed loop: when it was
sent), so a stall in the generator or the server counts against every
request it delays.  ``Record.late_s`` says how late the generator ran.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, List, Optional

import numpy as np


@dataclass
class Record:
    """One request of the run: when it was due, when it was sent, the
    pool entry it carried and the server's request handle."""

    due: float
    sent: float
    rows: int
    pool_index: int
    req: Any = None

    @property
    def late_s(self) -> float:
        return self.sent - self.due

    def outcome(self) -> Optional[Any]:
        """The settled result, or ``None`` while it has not settled."""
        if self.req is None:
            return None
        try:
            return self.req.wait(0)
        except TimeoutError:
            return None

    def latency_s(self) -> float:
        """Due to completion; infinite for a request that failed or has
        not completed."""
        res = self.outcome()
        if res is None or res.error is not None:
            return float("inf")
        return res.completed_at - self.due


def arrival_offsets(traffic: dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival offsets in ``[0, seconds)`` for an open loop."""
    count = int(round(traffic["rate_per_s"] * seconds))
    kind = traffic.get("arrival", "poisson")
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    return np.sort(rng.random(count)) * seconds


def run_open(server, pool: np.ndarray, traffic: dict, seconds: float,
             rng: np.random.Generator, t0: float) -> List[Record]:
    """Submit the open-loop schedule starting at ``t0`` (a
    ``perf_counter`` time); returns every request due in the window.
    Never blocks on a reply."""
    offsets = arrival_offsets(traffic, seconds, rng)
    order = rng.permutation(len(offsets)) % len(pool)
    rows = int(traffic["rows"])
    records: List[Record] = []
    for off, j in zip(offsets, order):
        due = t0 + float(off)
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = Record(due=due, sent=time.perf_counter(), rows=rows,
                     pool_index=int(j))
        rec.req = server.submit(pool[j])
        records.append(rec)
    return records


def run_closed(server, pool: np.ndarray, traffic: dict, seconds: float,
               rng: np.random.Generator, t0: float) -> List[Record]:
    """``clients`` threads send requests back to back from ``t0`` until
    the window closes; returns every request sent in the window."""
    clients = int(traffic["clients"])
    rows = int(traffic["rows"])
    end = t0 + seconds
    order = rng.permutation(len(pool))
    per_client: List[List[Record]] = [[] for _ in range(clients)]

    def client(c: int) -> None:
        mine = per_client[c]
        i = c
        wait = t0 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        while True:
            now = time.perf_counter()
            if now >= end:
                return
            j = int(order[i % len(order)])
            rec = Record(due=now, sent=now, rows=rows, pool_index=j)
            rec.req = server.submit(pool[j])
            mine.append(rec)
            rec.req.wait()
            i += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted((r for recs in per_client for r in recs),
                  key=lambda r: r.due)


def run(server, pool: np.ndarray, traffic: dict, seconds: float,
        rng: np.random.Generator, t0: float) -> List[Record]:
    loop = traffic["loop"]
    if loop == "open":
        return run_open(server, pool, traffic, seconds, rng, t0)
    if loop == "closed":
        return run_closed(server, pool, traffic, seconds, rng, t0)
    raise ValueError(f"unknown loop {loop!r}")


def settle(records: List[Record], deadline: float) -> None:
    """Wait for every request, at most until ``deadline``
    (``perf_counter`` time)."""
    for rec in records:
        left = deadline - time.perf_counter()
        if rec.req is None or left <= 0:
            continue
        try:
            rec.req.wait(left)
        except TimeoutError:
            pass


def pool_size(traffic: dict, seconds: float) -> int:
    """Distinct query blocks the pool holds: open loops get one per
    request up to 16384, closed loops ``pool_requests``."""
    if traffic["loop"] == "open":
        return max(1, min(16384, int(round(traffic["rate_per_s"]
                                           * seconds))))
    return int(traffic.get("pool_requests", 64))
