#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for (``BENCHMARK.json``).  The last line of standard output is the
result; the last lines of standard error name each number compared
for ``correct`` beside its limit.  Exits non-zero, with no result, when
JAX finds no accelerator the benchmark knows, too few chips, or no
system under test beside the benchmark, and when a program compiled
inside the measured window: set-up has to warm every shape the window
uses.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the TPU runtime's logs go inside the checkout, not to a fixed /tmp path
# that two checkouts on one machine would share
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_traces", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def _finite(x):
    """JSON has no NaN or infinity: such a number becomes null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except (harness.NoChip, FileNotFoundError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 2
    compiled = out["run"]["compiles_in_window"]
    if compiled:
        print(f"bench/run.py: {compiled} programs compiled inside the "
              f"measured window; set-up did not warm every shape it uses",
              file=sys.stderr, flush=True)
        return 3
    sys.stderr.flush()
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
