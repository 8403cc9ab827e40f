"""Search-plan engine benchmark: reduced-scale Table II sweep.

Runs the KNN kernel (Pneumonia-style gallery, scaled down to CI size)
over 5 subarray sizes x 2 optimization targets — the Fig. 8 / Table II
DSE shape — three ways:

* **seed**   — the pre-engine executor path (`execute_unplanned`): the
  partitioned IR walked / re-traced on every point.
* **cold**   — the search-plan engine with an empty plan cache: per-
  geometry plan build + jit compile + execution.
* **cached** — the same sweep again: every point hits the process-wide
  plan cache (targets share geometry, so 5 plans serve 10 points).

Writes ``BENCH_engine.json`` with wall-clock for all three, the
cold/cached split, plan-cache counters, and the speedup of the engine
over the seed path (the PR gate is >= 3x).  Also asserts engine results
match the interpreted oracle on one sweep point.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (ArchSpec, clear_plan_cache, compile_fn,
                        plan_cache_stats)

from .common import banner, save_bench_json, table

SIZES = (16, 32, 64, 128, 256)
MODES = (("cam-based", "latency"), ("cam-power", "power"))


def knn_kernel(q, gallery):
    diff = q.unsqueeze(1).sub(gallery)
    d = diff.norm(p=2, dim=-1)
    return d.topk(5, largest=False)


def _sweep(execute, q, g, dim):
    """Compile + execute every (target, size) point; returns results."""
    out = []
    for _, target in MODES:
        for s in SIZES:
            arch = ArchSpec(rows=s, cols=s, banks=1024).with_target(target)
            prog = compile_fn(knn_kernel, [q, g], arch, value_bits=8)
            out.append(np.asarray(execute(prog, q, g)[1]))
    return out


def run(n_gallery: int = 2048, dim: int = 128, n_queries: int = 64):
    banner("Engine — reduced Table II sweep: seed executor vs search plans")
    rng = np.random.default_rng(0)
    q = rng.standard_normal((n_queries, dim)).astype(np.float32)
    g = rng.standard_normal((n_gallery, dim)).astype(np.float32)

    clear_plan_cache()
    t0 = time.time()
    seed_idx = _sweep(lambda p, *a: p.execute_unplanned(*a), q, g, dim)
    seed_s = time.time() - t0

    clear_plan_cache()
    t0 = time.time()
    cold_idx = _sweep(lambda p, *a: p(*a), q, g, dim)
    cold_s = time.time() - t0
    cold_stats = plan_cache_stats()

    t0 = time.time()
    warm_idx = _sweep(lambda p, *a: p(*a), q, g, dim)
    warm_s = time.time() - t0
    warm_stats = plan_cache_stats()

    for a, b, c in zip(seed_idx, cold_idx, warm_idx):
        assert np.array_equal(a, b) and np.array_equal(b, c), \
            "engine sweep results diverged from the seed executor"

    speedup_cold = seed_s / max(cold_s, 1e-9)
    speedup_warm = seed_s / max(warm_s, 1e-9)
    rows = [
        {"path": "seed executor", "wall_s": seed_s, "speedup": 1.0},
        {"path": "engine (cold compile)", "wall_s": cold_s,
         "speedup": speedup_cold},
        {"path": "engine (cached execute)", "wall_s": warm_s,
         "speedup": speedup_warm},
    ]
    print(table(rows))
    print(f"\nplan cache after cold sweep: {cold_stats}")
    print(f"plan cache after cached sweep: {warm_stats}")

    payload = {
        "sweep": {"sizes": list(SIZES),
                  "targets": [t for _, t in MODES],
                  "n_gallery": n_gallery, "dim": dim,
                  "n_queries": n_queries, "k": 5, "metric": "eucl"},
        "seed_s": round(seed_s, 3),
        "engine_cold_s": round(cold_s, 3),
        "engine_cached_s": round(warm_s, 3),
        "speedup_cold": round(speedup_cold, 2),
        "speedup_cached": round(speedup_warm, 2),
        "plan_cache_cold": cold_stats,
        "plan_cache_cached": warm_stats,
    }
    save_bench_json("engine", payload)

    assert speedup_cold >= 3.0, (
        f"engine (cold) only {speedup_cold:.2f}x over the seed executor "
        f"(gate: >= 3x); see BENCH_engine.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
