#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from.

    python bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control]

For each seed, in one process: a run of the cell (a shorter window at
the cell's own load, checked as every run is), one JSON line with its
checks; with ``--control``, also the control, the reference one
precision step down (or with a stated guarantee broken) put in the
program's place on queries drawn from the same seed, through the same
comparison.  Each control the configuration's kind names in its
``CONTROLS`` is read (float L2: the three bfloat16 passes written out,
and the backend's own ``Precision.HIGH``).
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402


def control_readings(jax, cfg: dict, seed: int) -> dict:
    kind = harness.load_kind(cfg)
    streams = harness.seed_streams(seed)
    gallery = kind.make_gallery(
        cfg, jax.random.wrap_key_data(np.asarray(streams["key"])))
    q = kind.make_queries(cfg, streams["pool"], 1, int(cfg["check_rows"]))[0]
    ref = kind.reference(cfg, gallery, q)
    out = {}
    for name, kw in kind.CONTROLS.items():
        v, i = kind.control(cfg, gallery, q, **kw)
        out[name] = kind.compare(cfg, gallery, q, v, i, ref)
    out["reference_self"] = kind.compare(cfg, gallery, q, *ref, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--no-runs", action="store_true")
    args = ap.parse_args()
    harness.use_system()
    import jax

    _, _, cfg, _ = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        if not args.no_runs:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   log=lambda s: None)
            print(json.dumps({"seed": seed, "correct": out["correct"],
                              "checks": out["checks"],
                              "metrics": out["metrics"],
                              "run": out["run"]}), flush=True)
        if args.control:
            print(json.dumps({"seed": seed,
                              **control_readings(jax, cfg, seed)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
