#!/usr/bin/env python3
"""Spreads of the end-to-end metrics in ``sets.py`` output.

    python bench/tools/spread.py <file.jsonl> [...]

For each cell, metric and set: the median, the quartiles as
``statistics.quantiles(values, n=4)`` gives them, and the spread, the
distance between the quartiles over the median; then the wider of the
sets' spreads, and five times it, the bound that spread suggests.
"""

import json
import statistics
import sys
from collections import defaultdict


def main() -> int:
    vals = defaultdict(list)
    for path in sys.argv[1:]:
        for line in open(path):
            rec = json.loads(line)
            res = rec.get("result")
            if not res or rec.get("trace"):
                continue
            for name, m in res["metrics"].items():
                vals[(rec["workload"], name, rec["set"])].append(m["value"])
    widest = defaultdict(float)
    for (cell, name, s), v in sorted(vals.items()):
        med = statistics.median(v)
        q1, _, q3 = statistics.quantiles(v, n=4) if len(v) > 1 else (med,) * 3
        spread = (q3 - q1) / med if med else float("nan")
        widest[(cell, name)] = max(widest[(cell, name)], spread)
        print(f"{cell} {name} set{s} n={len(v)} median={med!r} "
              f"q1={q1!r} q3={q3!r} spread={spread:.5f}")
    for (cell, name), sp in sorted(widest.items()):
        print(f"{cell} {name} widest={sp:.5f} five_times={5 * sp:.5f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
