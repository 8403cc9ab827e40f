"""From a profiler trace and ``repro.obs`` spans to the device numbers.

The JAX profiler writes an ``.xplane.pb``; :func:`load_xplane` turns it
into a plain document, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``, which is also
the format of the recorded trace the tests read.  :func:`reduce` takes
such a document and a window and gives a :class:`DeviceTrace`:

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to
  the window, averaged over the devices;
* per-module device time (the ``XLA Modules`` line), by module name
  with its ``(id)`` suffix dropped, so ``jit_chunk_fn(42)`` is
  ``jit_chunk_fn``;
* the longest idle gaps, each named by the host span of the program
  (``repro.obs``) open at its middle.

Host spans run on ``perf_counter``; the profiler has its own clock.  A
``bench.sync`` annotation written into the profiler's trace at a known
``perf_counter`` instant ties the two together.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench.sync"
#: host spans that name what the serving path was doing; per-request
#: windows cover everything and say nothing about one moment
_NOT_A_PLACE = ("request",)
TOP = 10


def load_xplane(path: str) -> dict:
    """The plain document of an ``.xplane.pb``: every device plane, and
    the host's ``bench.sync`` annotation."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
            else:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events if e.name == SYNC]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals: List[Tuple[float, float]], lo: float,
          hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Covered length of ``intervals`` (start, end) clipped to
    ``[lo, hi]``, and the uncovered gaps as ``(start, end)``."""
    busy = 0.0
    gaps = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


@dataclass
class DeviceTrace:
    """The reduced trace of one window (seconds throughout)."""

    window_s: float
    busy_s: float
    devices: int
    #: module name -> [calls, device seconds]
    modules: Dict[str, List[float]] = field(default_factory=dict)
    #: op name -> device seconds
    ops: Dict[str, float] = field(default_factory=dict)
    #: the longest idle gaps: [label, seconds]
    gaps: List[List] = field(default_factory=list)

    def module_time(self, part: str) -> Tuple[int, float]:
        """Calls and device seconds of the modules whose name holds
        ``part``, over all devices."""
        calls = sum(int(v[0]) for k, v in self.modules.items() if part in k)
        secs = sum(v[1] for k, v in self.modules.items() if part in k)
        return calls, secs

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": self.gaps[:TOP]}


def reduce(doc: dict, lo_ns: float, hi_ns: float,
           label: Optional[callable] = None) -> Optional[DeviceTrace]:
    """Reduce ``doc`` over the window ``[lo_ns, hi_ns]`` of the trace's
    clock.  ``label(start_ns, end_ns)`` names an idle gap.  ``None``
    when the trace holds no device."""
    devices = [p for p in doc["planes"] if p["name"].startswith("/device:")
               and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if not devices:
        return None
    busy_total = 0.0
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    all_gaps = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        spans = []
        for name, start, dur in lines[OPS_LINE]:
            s, e = max(start, lo_ns), min(start + dur, hi_ns)
            if e > s:
                spans.append((start, start + dur))
                ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
        busy, gaps = union(spans, lo_ns, hi_ns)
        busy_total += busy
        all_gaps.extend(gaps)
        for name, start, dur in lines.get(MODULES_LINE, []):
            if lo_ns <= start + dur / 2 <= hi_ns:     # calls by midpoint
                m = modules.setdefault(_module_name(name), [0, 0.0])
                m[0] += 1
                m[1] += dur / 1e9
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = [[label(s, e) if label else "idle", (e - s) / 1e9]
             for s, e in all_gaps[:TOP]]
    return DeviceTrace(window_s=(hi_ns - lo_ns) / 1e9,
                       busy_s=busy_total / len(devices) / 1e9,
                       devices=len(devices), modules=modules, ops=ops,
                       gaps=named)


def sync_ns(doc: dict) -> Optional[float]:
    """Start of the ``bench.sync`` annotation on the trace's clock."""
    for plane in doc["planes"]:
        for line in plane["lines"]:
            for name, start, _dur in line["events"]:
                if name == SYNC:
                    return start
    return None


def host_spans(chrome: dict, obs_sync_ns: int, t0: float,
               t1: float) -> Tuple[dict, list]:
    """``repro.obs`` spans that start in the window ``[t0, t1]``
    (``perf_counter`` seconds): per-name stats ``{count, total_ms,
    mean_ms}`` and the intervals ``(name, start_s, end_s)``.  The
    chrome export counts microseconds from its first event; the
    ``bench.sync`` instant at ``obs_sync_ns`` anchors it."""
    evs = chrome["traceEvents"]
    base = next((e["ts"] for e in evs
                 if e.get("name") == SYNC and e.get("ph") == "i"), None)
    if base is None:
        return {}, []

    def perf_s(ts_us: float) -> float:
        return (obs_sync_ns + (ts_us - base) * 1e3) / 1e9

    intervals = []
    open_b: Dict[tuple, list] = {}
    for e in evs:
        ph = e.get("ph")
        if ph == "B":
            open_b.setdefault((e["pid"], e["tid"]), []).append(e)
        elif ph == "E":
            stack = open_b.get((e["pid"], e["tid"]))
            if stack:
                b = stack.pop()
                intervals.append((b["name"], perf_s(b["ts"]),
                                  perf_s(e["ts"])))
        elif ph == "X":
            intervals.append((e["name"], perf_s(e["ts"]),
                              perf_s(e["ts"] + e.get("dur", 0.0))))
    intervals = [iv for iv in intervals if t0 <= iv[1] <= t1]
    stats: Dict[str, dict] = {}
    for name, s, e in intervals:
        st = stats.setdefault(name, {"count": 0, "total_ms": 0.0})
        st["count"] += 1
        st["total_ms"] += (e - s) * 1e3
    for st in stats.values():
        st["mean_ms"] = st["total_ms"] / st["count"]
    return stats, intervals


def gap_labeller(intervals: list, offset_ns: float):
    """``label(start_ns, end_ns)`` for trace-clock gaps: the innermost
    host span open at the gap's middle (the latest to start), or
    ``no host span``.  ``offset_ns`` is perf_counter minus trace clock."""
    places = [iv for iv in intervals if not iv[0].startswith(_NOT_A_PLACE)]

    def label(s: float, e: float) -> str:
        mid = ((s + e) / 2 + offset_ns) / 1e9
        open_ = [iv for iv in places if iv[1] <= mid <= iv[2]]
        if not open_:
            return "no host span"
        return max(open_, key=lambda iv: iv[1])[0]

    return label


def reduce_xplane(path: str, *, sync: int, t0: float, t1: float,
                  intervals: list) -> Optional[DeviceTrace]:
    """Load and reduce one window of a profiler trace.  ``sync`` is the
    ``perf_counter_ns`` taken inside the ``bench.sync`` annotation;
    ``t0``/``t1`` bound the window in ``perf_counter`` seconds."""
    if t1 <= t0:
        return None
    doc = load_xplane(path)
    anchor = sync_ns(doc)
    if anchor is None:
        return None
    offset = sync - anchor                 # perf_counter ns - trace ns
    lo, hi = t0 * 1e9 - offset, t1 * 1e9 - offset
    return reduce(doc, lo, hi, gap_labeller(intervals, offset))
