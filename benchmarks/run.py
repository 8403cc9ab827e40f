"""Run the full benchmark suite: every paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig8 knn   # substring filter

Each suite additionally writes a ``BENCH_<name>.json`` timing record at
the repo root — wall-clock plus the search-plan-cache hit/miss deltas —
so future perf PRs have a measured baseline to compare against.

Each suite runs in a child process of its own and this parent never
touches a device: an accelerator belongs to one process at a time, and
some suites (serve, tune) start children of their own that need it.
"""

from __future__ import annotations

import importlib
import os
import subprocess
import sys
import time
import traceback

from .common import ROOT, banner, save_bench_json

#: (suite name, benchmarks module whose ``run()`` it calls)
SUITES = [
    ("fig7_validation", "fig7_validation"),
    ("fig8_dse", "fig8_dse"),
    ("table1_density", "table1_density"),
    ("table2_knn", "table2_knn"),
    ("fig9_isocapacity", "fig9_isocapacity"),
    ("gpu_comparison", "gpu_comparison"),
    ("roofline_table", "roofline_table"),
    # writes the detailed BENCH_engine.json itself; the generic record
    # for this suite lands in BENCH_engine_smoke.json
    ("engine_smoke", "bench_engine"),
    # packed XOR+popcount vs float hamming plans; detailed record in
    # BENCH_packed.json (gate REPRO_PACKED_GATE, auto = 4x at dim 1024)
    ("packed_smoke", "bench_packed"),
    # single- vs multi-device serving (subprocesses with their own
    # XLA_FLAGS); detailed record in BENCH_serve.json
    ("serve_smoke", "bench_serve"),
    # decision-forest aCAM range path vs interpreter oracle; detailed
    # record in BENCH_forest.json (gate REPRO_FOREST_GATE, auto = 2x)
    ("forest_smoke", "bench_forest"),
    # incremental update_rows vs full gallery re-prepare + HDC retrain
    # record; detailed record in BENCH_hdc.json (REPRO_HDC_GATE, auto = 3x)
    ("hdc_smoke", "bench_hdc"),
    # accuracy under injected device faults (unhardened vs HardenedPlan)
    # + resilient serving through transient outages; detailed record in
    # BENCH_faults.json (gate REPRO_FAULTS_GATE, auto = 0.9x clean)
    ("faults_smoke", "bench_faults"),
    # hierarchical coarse->fine probing vs the flat oracle at a 131k-row
    # packed gallery; detailed record in BENCH_hier.json (gate
    # REPRO_HIER_GATE, auto = 3x at the tuned recall>=0.95 nprobe)
    ("hier_smoke", "bench_hier"),
    # multi-tenant gateway: hot-tenant isolation (admission control vs a
    # naive shared server) + replica-kill failover; detailed record in
    # BENCH_multitenant.json (gate REPRO_MULTITENANT_GATE, auto = 2x
    # isolation factor)
    ("multitenant_smoke", "bench_multitenant"),
    # searched plans vs heuristic geometry + plan-store warm start
    # (cold/warm subprocesses); detailed record in BENCH_tune.json
    # (gates REPRO_TUNE_GATE, auto = 1.2x tuned speedup on >= 1 shape;
    # REPRO_TUNE_WARM_GATE, auto = 3x faster start-to-first-result)
    ("tune_smoke", "bench_tune"),
    # repro.obs tracing overhead: disabled-path cost per call site and
    # enabled wall-clock tax; detailed record in BENCH_trace.json (gate
    # REPRO_TRACE_GATE, auto = 1% disabled / 10% enabled)
    ("trace_smoke", "bench_trace"),
    # measured span timings vs the streaming-memory roofline; flags the
    # worst under-roofline kernel stage (the ranking that drove the
    # occupancy-bounded probe budget); detailed record in
    # BENCH_roofline_report.json
    ("roofline_report_smoke", "report_roofline"),
]


def _run_suite(name: str) -> int:
    """Child side: run one suite in this process and record it."""
    from repro.core import plan_cache_stats

    module = dict(SUITES)[name]
    fn = importlib.import_module(f"benchmarks.{module}").run
    t0 = time.time()
    cache0 = plan_cache_stats()
    try:
        fn()
    except Exception as e:                         # noqa: BLE001
        traceback.print_exc()
        save_bench_json(name, {"benchmark": name, "status": "fail",
                               "wall_s": round(time.time() - t0, 3),
                               "error": f"{type(e).__name__}: {e}"})
        print(f"\n[FAIL] {name}: {type(e).__name__}: {e}")
        return 1
    elapsed = time.time() - t0
    cache1 = plan_cache_stats()
    save_bench_json(name, {
        "benchmark": name, "status": "pass",
        "wall_s": round(elapsed, 3),
        "plan_cache": {
            "hits": cache1["hits"] - cache0["hits"],
            "misses": cache1["misses"] - cache0["misses"],
            "plans_total": cache1["plans"],
        }})
    print(f"\n[PASS] {name} ({elapsed:.1f}s)")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    if argv[:1] == ["--suite"]:
        return _run_suite(argv[1])
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    failures = []
    t00 = time.time()
    for name, _ in SUITES:
        if argv and not any(a in name for a in argv):
            continue
        rc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--suite", name],
            env=env, cwd=ROOT).returncode
        if rc:
            failures.append(name)
    banner(f"benchmark suite done in {time.time() - t00:.1f}s — "
           f"{'ALL PASS' if not failures else 'FAILURES: ' + ', '.join(failures)}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
