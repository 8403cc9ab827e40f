#!/usr/bin/env python3
"""Record a short profiler trace of a traced run, for the trace tests.

    python bench/tools/record_trace.py --workload <cell> --seed <n> \
        --seconds 2 --trace-s 0.03 [--set n=8192 ...] --out <dir>

Runs the cell once with ``--trace 1``, the configuration's keys changed
as ``--set`` says, the profiler recording the window's last
``--trace-s`` seconds.  Writes ``<dir>/trace.xplane.pb.gz``, the
profile as the profiler wrote it, and ``<dir>/trace.json``, what the
reduction read beside it and what it gave, which
``bench/test_bench_trace.py`` reduces again on the CPU.  Needs the
chip; a short trace keeps the profile small.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace-s", type=float, default=0.03)
    ap.add_argument("--set", action="append", default=[],
                    help="a configuration key=value (a JSON value)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        key, value = kv.split("=", 1)
        overrides[key] = json.loads(value)
    out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           overrides=overrides, trace_s=args.trace_s,
                           keep_trace=args.out)
    print(json.dumps({"correct": out["correct"], "device": out["device"],
                      "metrics": out["metrics"],
                      "breakdown": out.get("breakdown")}), flush=True)
    return 0 if os.path.isfile(os.path.join(args.out, "trace.json")) else 1


if __name__ == "__main__":
    sys.exit(main())
