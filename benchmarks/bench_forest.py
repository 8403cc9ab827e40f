"""Forest inference benchmark: engine RangePlan vs interpreter oracle.

Compiles a synthetic decision forest onto an analog CAM
(`repro.forest`) and times the same interval-match program two ways:

* **engine**      — the compiled ``RangePlan`` (jitted row-tile scan,
  micro-batched queries, memoised interval layout behind the plan
  cache),
* **interpreter** — ``execute_module`` on the partitioned IR (the
  semantic oracle: dense ``ref.acam_match``, re-dispatched eagerly on
  every call).

Predictions must agree bit-for-bit before any timing counts (the gate
is meaningless otherwise).  A plain per-sample Python tree traversal is
timed once for the record.  Writes ``BENCH_forest.json``; the gate is
the engine speedup over the interpreter at the large point:
``REPRO_FOREST_GATE=auto`` -> 2.0, any float overrides, ``0``/``off``
disables.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import clear_plan_cache
from repro.core.envcfg import env_gate
from repro.core.arch import ArchSpec, CamType
from repro.core.executor import execute_module
from repro.forest import CamForestClassifier, random_forest, vote

from .common import banner, save_bench_json, table

#: (n_trees, depth, dim, m_queries); the first point carries the gate
POINTS = ((64, 6, 64, 256), (32, 4, 32, 128))
N_CLASSES = 8
REPEATS = 5


def _time(fn) -> float:
    fn()                                    # warmup (compile + prepare)
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _gate() -> float:
    return env_gate("REPRO_FOREST_GATE", 2.0)


def run():
    banner("Forest inference — engine RangePlan vs interpreter oracle")
    rng = np.random.default_rng(0)
    rows, results = [], {}
    for n_trees, depth, dim, m in POINTS:
        clear_plan_cache()
        trees = random_forest(rng, n_trees=n_trees, dim=dim, depth=depth,
                              n_classes=N_CLASSES, feature_frac=0.5)
        arch = ArchSpec(rows=64, cols=64, cam_type=CamType.ACAM)
        clf = CamForestClassifier(trees, dim=dim).compile(arch,
                                                          batch_hint=m)
        x = rng.standard_normal((m, dim)).astype(np.float32)
        iv = clf.intervals
        mod = clf.stages["cim_partitioned"]

        # the gate is only meaningful if the paths agree bit-for-bit
        pe = clf.predict(x)
        assert np.array_equal(pe, clf.predict_interpreted(x)), \
            "engine predictions diverged from the interpreter oracle"
        assert np.array_equal(pe, clf.predict_reference(x)), \
            "engine predictions diverged from tree traversal"

        def engine():
            m_ = clf.matches(x)
            vote(m_, iv.leaf_class, iv.n_classes)

        def interp():
            m_ = np.asarray(execute_module(mod, x, iv.lo, iv.hi)[0])
            vote(m_, iv.leaf_class, iv.n_classes)

        t_engine = _time(engine)
        t_interp = _time(interp)
        t_traverse = _time(lambda: clf.predict_reference(x))

        speedup = t_interp / max(t_engine, 1e-9)
        key = f"t{n_trees}_d{depth}"
        results[key] = {
            "n_trees": n_trees, "depth": depth, "dim": dim, "m": m,
            "rows": iv.n_rows,
            "wildcard_frac": round(iv.wildcard_frac, 4),
            "engine_ms": round(1e3 * t_engine, 2),
            "interp_ms": round(1e3 * t_interp, 2),
            "traverse_ms": round(1e3 * t_traverse, 2),
            "speedup": round(speedup, 2),
        }
        rows.append({"trees": n_trees, "rows": iv.n_rows, "m": m,
                     "engine_ms": 1e3 * t_engine,
                     "interp_ms": 1e3 * t_interp,
                     "traverse_ms": 1e3 * t_traverse, "speedup": speedup})
    print(table(rows))

    gate = _gate()
    first = POINTS[0]
    gated = results[f"t{first[0]}_d{first[1]}"]
    payload = {
        "points": results,
        "repeats": REPEATS,
        "gate": gate,
        "gate_point": f"t{first[0]}_d{first[1]}",
        "speedup": gated["speedup"],
    }
    save_bench_json("forest", payload)
    if gate:
        assert gated["speedup"] >= gate, (
            f"forest RangePlan only {gated['speedup']:.2f}x over the "
            f"interpreter oracle (gate: >= {gate}x); see BENCH_forest.json")
        # the small point regressed below 1.0x before the tiny-plan
        # dense fast path (per-tile lax.scan stepping dominated the
        # arithmetic at a few hundred rows); pin it at parity-or-better
        # so the fast path cannot rot silently
        small = POINTS[-1]
        small_speedup = results[f"t{small[0]}_d{small[1]}"]["speedup"]
        assert small_speedup >= 1.0, (
            f"small-program point t{small[0]}_d{small[1]} fell back below "
            f"the interpreter ({small_speedup:.2f}x < 1.0x): the tiny-plan "
            f"fast path regressed; see BENCH_forest.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
