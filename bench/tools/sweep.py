#!/usr/bin/env python3
"""Find the highest open-loop rate a cell's server sustains.

    python bench/tools/sweep.py --workload <open-loop cell> --seed <n> \
        --rates 200,300,400 --seconds 8

One process, one set-up; then, for each rate in turn, the cell's mix at
that rate for ``--seconds``, every request waited for before the next
rate.  Prints one JSON line per rate: requests due, completed by the
window's close, the backlog at the middle and at the close, the
completion rate over the second half against the arrival rate, and the
latency percentiles.  A rate is sustained where the backlog does not
grow from the middle to the close and completions keep up with
arrivals.
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402
import loadgen  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    harness.use_system()
    import jax

    _, cell, cfg, traffic = harness.load_cell(args.workload)
    harness.device_info(jax, int(cell["chips"]),
                        harness.load_json(os.path.join(harness.BENCH_DIR,
                                                       "peaks.json")))
    harness.enable_compile_cache(jax, harness.ROOT)
    rates = [float(r) for r in args.rates.split(",")]
    longest = {**traffic, "rate_per_s": max(rates)}
    _, streams, _, _, pool, server = harness.start_server(
        jax, cfg, longest, args.seed, args.seconds)
    try:
        for rate in rates:
            mix = {**traffic, "rate_per_s": rate}
            s0 = dict(server.stats)
            t0 = time.perf_counter() + 0.01
            recs = loadgen.run(server, pool, mix, args.seconds,
                               streams["traffic"], t0)
            t1 = t0 + args.seconds
            left = t1 - time.perf_counter()
            if left > 0:
                time.sleep(left)
            loadgen.settle(recs, t1 + 60.0)
            s1 = dict(server.stats)
            mid = t0 + args.seconds / 2
            done = [r.outcome() for r in recs]
            comp = [d.completed_at if d is not None and d.error is None
                    else math.inf for d in done]
            due_mid = sum(r.due <= mid for r in recs)
            due_end = len(recs)
            by_mid = sum(c <= mid for c in comp)
            by_end = sum(c <= t1 for c in comp)
            second_half = sum(mid < c <= t1 for c in comp)
            lat = sorted(r.latency_s() for r in recs)
            pick = lambda q: lat[max(0, math.ceil(q * len(lat)) - 1)]  # noqa: E731
            batches = s1["batches"] - s0["batches"]
            print(json.dumps({
                "rate": rate, "due": due_end, "completed_by_close": by_end,
                "backlog_mid": due_mid - by_mid,
                "backlog_close": due_end - by_end,
                "completion_rate_second_half":
                    second_half / (args.seconds / 2),
                "arrival_rate_second_half":
                    (due_end - due_mid) / (args.seconds / 2),
                "p50_ms": 1e3 * pick(0.5), "p99_ms": 1e3 * pick(0.99),
                "rows_per_batch": (s1["batched_rows"] - s0["batched_rows"])
                / batches if batches else None,
                "late_max_s": max(r.late_s for r in recs)}), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
