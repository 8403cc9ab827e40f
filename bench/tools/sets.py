#!/usr/bin/env python3
"""Run a cell several times in a row, each run a process of its own.

    python bench/tools/sets.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--trace 0|1] [--repeat 2] --out <file.jsonl>

This process never touches JAX, so each child has the chip to itself.
``--repeat 2`` runs the seed list twice (two sets with the same seeds).
Each run adds one line to ``--out``: the set, the seed, the exit code,
the wall seconds, the result line (or ``null``) and the end of standard
error.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="20")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--timeout", type=float, default=420.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = args.seeds.split(",")
    with open(args.out, "a") as f:
        for s in range(args.repeat):
            for seed in seeds:
                cmd = [sys.executable, os.path.join("bench", "run.py"),
                       "--workload", args.workload, "--seed", seed,
                       "--seconds", args.seconds, "--trace", args.trace]
                t = time.perf_counter()
                try:
                    p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                       text=True, timeout=args.timeout)
                    rc, out, err = p.returncode, p.stdout, p.stderr
                except subprocess.TimeoutExpired as e:
                    rc, out, err = 124, e.stdout or "", e.stderr or ""
                    out = out if isinstance(out, str) else out.decode()
                    err = err if isinstance(err, str) else err.decode()
                wall = time.perf_counter() - t
                lines = out.strip().splitlines()
                try:
                    result = json.loads(lines[-1]) if lines else None
                except json.JSONDecodeError:
                    result = None
                rec = {"workload": args.workload, "set": s,
                       "seed": int(seed), "trace": int(args.trace),
                       "rc": rc, "wall_s": wall, "result": result,
                       "stderr_tail": err[-1500:]}
                f.write(json.dumps(rec) + "\n")
                f.flush()
                m = (result or {}).get("metrics", {})
                print(json.dumps({"set": s, "seed": seed, "rc": rc,
                                  "wall_s": round(wall, 1),
                                  "correct": (result or {}).get("correct"),
                                  "metrics": {k: v["value"]
                                              for k, v in m.items()}}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
