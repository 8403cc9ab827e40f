"""Measured roofline report: trace spans joined against the model.

Where ``benchmarks/roofline_table.py`` prints the *modelled* roofline
(dry-run artifacts: per arch x shape the compute/memory/collective
terms), this report measures the real kernels with ``repro.obs``
tracing and scores each stage against a streaming-memory roofline:

* calibrate host stream bandwidth (large-block copy),
* run a traced flat packed scan and a traced hierarchical coarse→fine
  search on the same gallery,
* per stage (flat scan, ``hier.coarse``, ``hier.probe``) compute the
  bytes the stage streams, its achieved GB/s, and the roofline
  fraction (achieved / calibrated stream bandwidth).  The hier stage
  spans block on their device results under tracing, so their span
  durations are real stage time; the flat plan's ``plan.dispatch``
  span is jax-async (it times dispatch latency, not device work —
  see docs/observability.md), so the flat stage is measured by wall
  clock around the whole execute instead,
* flag the **worst under-roofline stage** among stages big enough to
  be bandwidth-bound (tiny latency-bound stages are reported but not
  ranked).

This ranking is what motivated the occupancy-bounded probe budget in
``repro.core.engine.hier``: ``hier.probe`` sat far under the flat
scan's fraction because the uniform tiles-per-cluster padding gathered
~1.8x the tiles the cluster occupancy distribution requires (416
padded steps vs 235 occupied at nprobe=16).  The fix is gated in
``BENCH_hier.json`` (``wide`` entry).

Joins the dry-run roofline table (``artifacts/bench/
roofline_table.json``, written by ``benchmarks.roofline_table``) when
present; missing artifacts degrade to the measured-only report.
Writes ``BENCH_roofline_report.json``.
"""

from __future__ import annotations

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.core import ArchSpec, Builder, Module, PassManager, TensorType, \
    clear_plan_cache, get_plan
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.engine import get_hierarchical_plan
from repro.core.passes import CompulsoryPartition
from repro.obs import trace as _trace

from .common import ART, banner, save_bench_json, table

N_GALLERY = 131_072
DIM = 256
K = 10
M_QUERIES = 64
CLUSTERS = 128
NPROBE = 16
KMEANS_ITERS = 4
TRACED_RUNS = 3
#: stages streaming less than this are latency-bound, not rankable
#: against a bandwidth roofline
MIN_RANKABLE_BYTES = 1 << 20


def _module(m, n, dim, k, arch):
    mod = Module("roofline_report",
                 [TensorType((m, dim)), TensorType((n, dim))])
    q, p = mod.arguments
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, [q, p],
                       [TensorType((m, k)), TensorType((m, k), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, q, p, metric="hamming", k=k, largest=False,
                          extra_attrs={"value_bits": 1})
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition(unroll_limit=64))
    return pm.run(mod, {"arch": arch})


def _stream_bandwidth_gbs() -> float:
    """Calibrated host stream bandwidth: best-of large-block copy."""
    a = np.ones(1 << 26, np.uint8)              # 64 MiB
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        b = a.copy()
        best = min(best, time.perf_counter() - t0)
        del b
    # a copy reads + writes the block
    return 2 * a.nbytes / best / 1e9


def _traced_stats(run_fn):
    """Run ``run_fn`` TRACED_RUNS times under tracing, return the
    per-span aggregate (total over all runs)."""
    was_enabled = _trace.tracer.enabled
    _trace.tracer.clear()
    _trace.enable()
    try:
        for _ in range(TRACED_RUNS):
            run_fn()
    finally:
        if not was_enabled:
            _trace.stop()
    stats = _trace.span_stats()
    _trace.tracer.clear()
    return stats


def _probe_budget_from_events() -> int:
    """The static probe budget the traced run used (span args)."""
    for ph, name, _pid, _tid, _ts, _dur, args in _trace.tracer._events:
        if name == "hier.probe" and args:
            return int(args.get("budget", 0))
    return 0


def _load_modelled_cells():
    path = os.path.join(ART, "roofline_table.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            rows = json.load(f)
    except (OSError, ValueError):
        return None
    return [{"arch": r.get("arch"), "shape": r.get("shape"),
             "bottleneck": r.get("bottleneck"),
             "roofline_frac": r.get("roofline_frac")}
            for r in rows] or None


def run():
    banner("Roofline report — measured span timings vs the stream model")
    rng = np.random.default_rng(0)
    clear_plan_cache()
    bw = _stream_bandwidth_gbs()
    print(f"calibrated stream bandwidth: {bw:.1f} GB/s")

    arch = ArchSpec(rows=128, cols=128)
    mod = _module(M_QUERIES, N_GALLERY, DIM, K, arch)
    g = jnp.asarray((rng.random((N_GALLERY, DIM)) > 0.5)
                    .astype(np.float32))
    q = (rng.random((M_QUERIES, DIM)) > 0.5).astype(np.float32)

    flat = get_plan(mod)
    hier = get_hierarchical_plan(mod, clusters=CLUSTERS, nprobe=NPROBE,
                                 kmeans_iters=KMEANS_ITERS)
    for plan in (flat, hier):                   # compile + prepare
        v, i = plan.execute(q, g)
        np.asarray(v), np.asarray(i)

    # flat stage: wall clock (the plan.dispatch span is async — it
    # times dispatch latency, not device work)
    flat_ms = float("inf")
    for _ in range(TRACED_RUNS):
        t0 = time.perf_counter()
        v, i = flat.execute(q, g)
        np.asarray(v), np.asarray(i)
        flat_ms = min(flat_ms, 1e3 * (time.perf_counter() - t0))

    budget = 0

    def run_hier():
        v, i = hier.execute(q, g)
        np.asarray(v), np.asarray(i)

    was_enabled = _trace.tracer.enabled
    _trace.tracer.clear()
    _trace.enable()
    try:
        for _ in range(TRACED_RUNS):
            run_hier()
        budget = _probe_budget_from_events()
    finally:
        if not was_enabled:
            _trace.stop()
    hier_stats = _trace.span_stats()
    _trace.tracer.clear()

    row_bytes = DIM // 8                        # packed hamming row
    tile_rows = arch.rows

    def _per_run(st):
        return None if st is None else st["total_ms"] / st["count"]

    stages = {
        # the flat scan matches every query against every packed row
        "flat.scan": (flat_ms, M_QUERIES * N_GALLERY * row_bytes),
        # coarse stage: every query against the centroid table
        "hier.coarse": (_per_run(hier_stats.get("hier.coarse")),
                        M_QUERIES * CLUSTERS * row_bytes),
        # fine stage: per query, gather `budget` tiles of `tile_rows`
        # packed rows (random access — no cross-query reuse)
        "hier.probe": (_per_run(hier_stats.get("hier.probe")),
                       M_QUERIES * budget * tile_rows * row_bytes),
    }
    rows, report = [], {}
    worst = None
    for name, (ms, bytes_per_run) in stages.items():
        if ms is None:
            continue
        gbs = bytes_per_run / (ms / 1e3) / 1e9 if ms > 0 else 0.0
        frac = gbs / bw if bw > 0 else 0.0
        rankable = bytes_per_run >= MIN_RANKABLE_BYTES
        entry = {"measured_ms": round(ms, 2),
                 "bytes_per_run": int(bytes_per_run),
                 "achieved_gbs": round(gbs, 2),
                 "roofline_frac": round(frac, 4),
                 "rankable": rankable}
        report[name] = entry
        rows.append({"stage": name, **entry})
        if rankable and (worst is None
                         or frac < report[worst]["roofline_frac"]):
            worst = name
    print(table(rows))
    if worst:
        print(f"\nworst under-roofline stage: {worst} "
              f"({report[worst]['roofline_frac']:.3f} of stream roofline)")

    modelled = _load_modelled_cells()
    if modelled is None:
        print("no dry-run roofline artifacts "
              f"({os.path.join(ART, 'roofline_table.json')}) — "
              "measured-only report; run benchmarks.roofline_table to "
              "join the modelled cells")

    payload = {
        "workload": {"n_gallery": N_GALLERY, "dim": DIM, "k": K,
                     "m_queries": M_QUERIES, "clusters": CLUSTERS,
                     "nprobe": NPROBE, "probe_budget": budget,
                     "traced_runs": TRACED_RUNS, "metric": "hamming",
                     "packed": True},
        "stream_bandwidth_gbs": round(bw, 2),
        "stages": report,
        "worst_stage": worst,
        "modelled_cells": modelled,
        "fix": {
            "stage": "hier.probe",
            "change": "occupancy-bounded probe budget "
                      "(repro.core.engine.hier._probe_budget): size the "
                      "fine gather by the top-nprobe occupied-tile "
                      "counts instead of uniform tiles-per-cluster "
                      "padding",
            "gate": "BENCH_hier.json wide entry "
                    "(REPRO_HIER_WIDE_GATE)",
        },
    }
    save_bench_json("roofline_report", payload)
    assert report, "no stages measured — tracing produced no spans"
    assert worst is not None, "no bandwidth-rankable stage measured"
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
