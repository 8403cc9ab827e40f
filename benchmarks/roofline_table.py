"""Roofline table — aggregates the dry-run artifacts for all 40 cells.

Reads ``artifacts/dryrun/*.json`` (produced by `repro.launch.dryrun`) and
prints the §Roofline table: per (arch x shape x mesh) the three roofline
terms, the dominant bottleneck, MODEL_FLOPS/HLO_FLOPs, and the roofline
fraction.  This module does NOT lower anything itself (the dry-run needs
512 placeholder devices; run ``python -m repro.launch.dryrun`` first) —
if artifacts are missing it says so and exits cleanly.
"""

from __future__ import annotations

import glob
import json
import os

from .common import banner, save_json, table

DRYRUN_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts", "dryrun")


def load_cells(mesh: str = "16x16"):
    rows = []
    for f in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*_{mesh}.json"))):
        d = json.load(open(f))
        rows.append(d)
    return rows


def run():
    banner("Roofline table (from dry-run artifacts; single-pod 16x16)")
    cells = load_cells("16x16")
    if not cells:
        print("no dry-run artifacts found — run: "
              "PYTHONPATH=src python -m repro.launch.dryrun "
              "--arch all --shape all --mesh single")
        return []
    rows = []
    n_ok = n_skip = n_fail = 0
    for d in cells:
        if d.get("skipped"):
            rows.append({"arch": d["arch"], "shape": d["shape"],
                         "bottleneck": "SKIP (full attn)"})
            n_skip += 1
            continue
        if d.get("error"):
            rows.append({"arch": d["arch"], "shape": d["shape"],
                         "bottleneck": "FAIL"})
            n_fail += 1
            continue
        r = d["roofline"]
        n_ok += 1
        rows.append({
            "arch": d["arch"], "shape": d["shape"],
            "t_compute_s": r["t_compute"], "t_memory_s": r["t_memory"],
            "t_collective_s": r["t_collective"],
            "bottleneck": r["bottleneck"],
            "peak_GiB": d["memory"]["peak_bytes"] / 2 ** 30,
            "useful_flops": d.get("useful_flops_ratio") or 0.0,
            "roofline_frac": d.get("roofline_fraction") or 0.0,
        })
    print(table(rows))
    from repro.launch.roofline import bottleneck_advice
    print("\nWhat would move the dominant term (per cell):")
    for d in cells:
        if d.get("skipped") or d.get("error"):
            continue
        adv = bottleneck_advice(d["roofline"]["bottleneck"], d["kind"],
                                d.get("family", ""))
        print(f"  {d['arch']} x {d['shape']} "
              f"[{d['roofline']['bottleneck']}]: {adv}")
    print(f"\n{n_ok} baselined, {n_skip} skipped (documented), "
          f"{n_fail} failed")
    multi = load_cells("2x16x16")
    m_ok = sum(1 for d in multi if not d.get("skipped")
               and not d.get("error"))
    m_skip = sum(1 for d in multi if d.get("skipped"))
    print(f"multi-pod (2x16x16): {m_ok} compiled, {m_skip} skipped, "
          f"of {len(multi)} recorded")
    save_json("roofline_table", rows)
    assert n_fail == 0, "dry-run failures present"
    return rows


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
