"""Hierarchical search benchmark: two-stage coarse→fine vs flat scan.

Builds a CAM-scale packed hamming gallery (>= 100k rows, clustered the
way real retrieval corpora are) and runs the same top-k search two
ways:

* **flat** — the ordinary ``SearchPlan``: every row tile probed for
  every query (the bit-exact oracle),
* **hierarchical** — ``get_hierarchical_plan``: a coarse centroid
  search picks ``nprobe`` clusters per query, the fine stage probes
  only those clusters' tiles.

For each ``nprobe`` in the sweep the recall against the flat oracle's
top-k, the wall-clock speedup, and a trace-derived coarse/probe stage
breakdown (``repro.obs`` spans) are recorded.  Writes
``BENCH_hier.json``; two gates:

* **tuned** — the smallest swept ``nprobe`` whose recall clears
  ``RECALL_FLOOR`` (0.95) must beat the flat plan by
  ``REPRO_HIER_GATE`` (``auto`` -> 3.0, any float overrides,
  ``0``/``off`` disables);
* **wide** — the widest swept ``nprobe`` must not *lose* to the flat
  plan (``REPRO_HIER_WIDE_GATE``, ``auto`` -> 1.0).  Before the
  occupancy-bounded probe budget (the fix the roofline report drove)
  nprobe=16 ran at 0.82x — uniform tiles-per-cluster padding made the
  fine gather touch ~1.8x the steps the occupancy distribution needs.

Bit-identity at ``nprobe == clusters`` is pinned by the test suite
(``tests/test_hier.py``, ``tests/test_parity_fuzz.py``), not re-timed
here.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import ArchSpec, Builder, Module, PassManager, TensorType, \
    clear_plan_cache, get_plan
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.engine import get_hierarchical_plan
from repro.core.envcfg import env_gate
from repro.core.passes import CompulsoryPartition
from repro.obs import trace as _trace

from .common import banner, save_bench_json, table

N_GALLERY = 131_072
DIM = 256
K = 10
M_QUERIES = 64
CLUSTERS = 128
NPROBES = (4, 8, 16)
KMEANS_ITERS = 4
REPEATS = 5
#: the tuned operating point must recall at least this much of the
#: flat oracle's top-k
RECALL_FLOOR = 0.95


def _gate() -> float:
    return env_gate("REPRO_HIER_GATE", 3.0)


def _wide_gate() -> float:
    return env_gate("REPRO_HIER_WIDE_GATE", 1.0)


def _stage_breakdown(plan, q, g):
    """One traced execute -> {coarse_ms, probe_ms} from the engine
    spans (off the timed path; the recorder is cleared afterwards)."""
    was_enabled = _trace.tracer.enabled
    _trace.tracer.clear()
    _trace.enable()
    try:
        v, i = plan.execute(q, g)
        np.asarray(v), np.asarray(i)
    finally:
        if not was_enabled:
            _trace.stop()
    stats = _trace.span_stats()
    out = {}
    for span, key in (("hier.coarse", "coarse_ms"),
                      ("hier.probe", "probe_ms")):
        if span in stats:
            out[key] = round(stats[span]["total_ms"], 2)
    _trace.tracer.clear()
    return out


def _hamming_module(m, n, dim, k, arch):
    mod = Module("hier_bench", [TensorType((m, dim)), TensorType((n, dim))])
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


def _clustered_gallery(rng, n, dim, centers, flip=0.05):
    """Binary rows drawn around ``centers`` prototypes — the locality a
    retrieval corpus has and the coarse stage exploits."""
    protos = (rng.random((centers, dim)) > 0.5)
    owner = rng.integers(centers, size=n)
    rows = protos[owner] ^ (rng.random((n, dim)) < flip)
    return rows.astype(np.float32)


def _time_plan(plan, q, g) -> float:
    v, i = plan.execute(q, g)                   # compile + prepare (warmup)
    np.asarray(v), np.asarray(i)
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        v, i = plan.execute(q, g)
        np.asarray(v), np.asarray(i)
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    banner("Hierarchical search — coarse→fine probing vs flat scan")
    rng = np.random.default_rng(0)
    clear_plan_cache()
    arch = ArchSpec(rows=128, cols=128)
    mod = _hamming_module(M_QUERIES, N_GALLERY, DIM, K, arch)

    g_np = _clustered_gallery(rng, N_GALLERY, DIM, CLUSTERS)
    # queries: perturbed gallery rows — nearest neighbours exist and are
    # cluster-local, the regime hierarchical probing is for
    qi = rng.choice(N_GALLERY, size=M_QUERIES, replace=False)
    q = (g_np[qi].astype(bool)
         ^ (rng.random((M_QUERIES, DIM)) < 0.05)).astype(np.float32)
    g = jnp.asarray(g_np)

    flat = get_plan(mod)
    assert flat.packed, "hamming at this geometry should auto-pack"
    t_flat = _time_plan(flat, q, g)
    fv, fi = flat.execute(q, g)
    flat_sets = [set(map(int, row)) for row in np.asarray(fi)]

    rows_out, sweep = [], {}
    for nprobe in NPROBES:
        plan = get_hierarchical_plan(mod, clusters=CLUSTERS, nprobe=nprobe,
                                     kmeans_iters=KMEANS_ITERS)
        t = _time_plan(plan, q, g)
        _, hi = plan.execute(q, g)
        recall = float(np.mean([
            len(set(map(int, row)) & fs) / K
            for row, fs in zip(np.asarray(hi), flat_sets)]))
        speedup = t_flat / max(t, 1e-9)
        stages = _stage_breakdown(plan, q, g)
        sweep[f"nprobe{nprobe}"] = {
            "nprobe": nprobe, "clusters": CLUSTERS,
            "probed_frac": round(nprobe / CLUSTERS, 4),
            "hier_ms": round(1e3 * t, 2),
            "recall": round(recall, 4),
            "speedup": round(speedup, 2),
            "stages": stages,
        }
        rows_out.append({"nprobe": nprobe, "hier_ms": 1e3 * t,
                         "flat_ms": 1e3 * t_flat, "recall": recall,
                         "speedup": speedup, **stages})
    print(table(rows_out))

    gate = _gate()
    wide_gate = _wide_gate()
    tuned = next((s for s in sweep.values() if s["recall"] >= RECALL_FLOOR),
                 None)
    wide = sweep[f"nprobe{max(NPROBES)}"]
    payload = {
        "workload": {"n_gallery": N_GALLERY, "dim": DIM, "k": K,
                     "m_queries": M_QUERIES, "clusters": CLUSTERS,
                     "kmeans_iters": KMEANS_ITERS, "metric": "hamming",
                     "packed": True},
        "flat_ms": round(1e3 * t_flat, 2),
        "sweep": sweep,
        "repeats": REPEATS,
        "recall_floor": RECALL_FLOOR,
        "gate": gate,
        "tuned": tuned,
        "wide_gate": wide_gate,
        "wide": wide,
    }
    save_bench_json("hier", payload)
    if wide_gate:
        assert wide["speedup"] >= wide_gate, (
            f"hierarchical plan at the widest probe "
            f"(nprobe={wide['nprobe']}) only {wide['speedup']:.2f}x over "
            f"the flat plan (gate: >= {wide_gate}x) — the occupancy-"
            f"bounded probe budget should keep the wide point ahead of "
            f"a dense scan; see BENCH_hier.json")
    if gate:
        assert tuned is not None, (
            f"no swept nprobe reached recall >= {RECALL_FLOOR} "
            f"(sweep: { {k: s['recall'] for k, s in sweep.items()} }); "
            f"see BENCH_hier.json")
        assert tuned["speedup"] >= gate, (
            f"hierarchical plan at nprobe={tuned['nprobe']} (recall "
            f"{tuned['recall']:.3f}) only {tuned['speedup']:.2f}x over the "
            f"flat plan (gate: >= {gate}x); see BENCH_hier.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
