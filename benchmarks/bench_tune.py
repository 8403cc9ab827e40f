"""Autotuner benchmark: searched plans vs heuristic plans + warm start.

Two claims, two measurements (``repro.tune``):

* **search pays** — for workloads whose arch-derived heuristic geometry
  is poor (paper-scale small subarrays -> deep serial tile scans), the
  coordinate-descent autotuner finds a verified plan >= 1.2x faster
  than the heuristic one on at least one swept shape
  (``REPRO_TUNE_GATE``).
* **the store kills cold starts** — with ``REPRO_PLAN_STORE``
  populated, a fresh process reaches its first search result >= 3x
  faster than the process that had to tune + XLA-compile from scratch
  (``REPRO_TUNE_WARM_GATE``), with **zero** tune trials, both stored
  executables adopted (zero XLA compiles), and bit-identical output.
  Each measurement runs in its own subprocess (cold-start is a
  process-lifetime property).

Writes ``BENCH_tune.json``.

    PYTHONPATH=src python -m benchmarks.bench_tune
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

from .common import banner, save_bench_json, table

_MARK = "TUNE-RESULT "
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: shapes whose heuristic geometry (tile_rows = arch rows, dims_per_tile
#: from arch cols) leaves obvious headroom for the search
SHAPES = [
    dict(name="hamming-512", metric="hamming", k=4, m=16, n=512, dim=64,
         rows=16, cols=32),
    dict(name="eucl-1k", metric="eucl", k=8, m=16, n=1024, dim=64,
         rows=16, cols=64),
    dict(name="dot-2k", metric="dot", k=8, m=32, n=2048, dim=64,
         rows=32, cols=64),
]
TRIALS = 10
REPS = 3

#: warm-start workload: non-tiny (n*dim clears REPRO_ENGINE_TINY_CELLS)
#: so the AOT-executable half of the store is on the measured path
WARM = dict(metric="hamming", k=8, m=32, n=4096, dim=64, rows=64, cols=64)
WARM_TRIALS = 6


def _gate() -> float:
    from repro.core.envcfg import env_gate
    return env_gate("REPRO_TUNE_GATE", 1.2)


def _warm_gate() -> float:
    from repro.core.envcfg import env_gate
    return env_gate("REPRO_TUNE_WARM_GATE", 3.0)


def _module(cfg):
    """Hand-built fused similarity module through the partition pass
    (same construction as the engine parity tests)."""
    from repro.core import (ArchSpec, Builder, Module, PassManager,
                            TensorType)
    from repro.core.cim_dialect import (make_acquire, make_execute,
                                        make_release, make_similarity,
                                        make_yield)
    from repro.core.passes import CompulsoryPartition

    m, n, dim, k = cfg["m"], cfg["n"], cfg["dim"], cfg["k"]
    mod = Module("bench_tune", [TensorType((m, dim)), TensorType((n, dim))])
    q, p = mod.arguments
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, [q, p],
                       [TensorType((m, k)), TensorType((m, k), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, q, p, metric=cfg["metric"], k=k,
                          largest=cfg["metric"] != "eucl")
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition())
    return pm.run(mod, {"arch": ArchSpec(rows=cfg["rows"],
                                         cols=cfg["cols"])})


def _data(cfg, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    m, n, dim = cfg["m"], cfg["n"], cfg["dim"]
    if cfg["metric"] == "hamming":
        return ((rng.random((m, dim)) > 0.5).astype(np.float32),
                (rng.random((n, dim)) > 0.5).astype(np.float32))
    return (rng.standard_normal((m, dim)).astype(np.float32),
            rng.standard_normal((n, dim)).astype(np.float32))


# ---------------------------------------------------------------------------
# part 1: tuned vs heuristic
# ---------------------------------------------------------------------------

def _sweep() -> list:
    from repro.tune import PlanStore, tune_plan

    # a private throwaway store so the sweep always searches (a
    # CI-configured REPRO_PLAN_STORE would otherwise short-circuit it)
    store = PlanStore(tempfile.mkdtemp(prefix="bench-tune-"))
    rows = []
    for cfg in SHAPES:
        mod = _module(cfg)
        q, p = _data(cfg)
        res = tune_plan(mod, q, p, trials=TRIALS, reps=REPS, store=store)
        rows.append({
            "shape": cfg["name"],
            "n": cfg["n"], "dim": cfg["dim"],
            "heuristic_ms": round(res.base_s * 1e3, 3),
            "tuned_ms": round(res.best_s * 1e3, 3),
            "speedup": round(res.speedup, 2),
            "trials": res.trials,
            "winner": {k: res.config[k] for k in
                       ("tile_rows", "dims_per_tile", "batch", "pack",
                        "unroll")},
        })
    return rows


# ---------------------------------------------------------------------------
# part 2: cold vs warm start (subprocesses sharing one store)
# ---------------------------------------------------------------------------

def _child() -> dict:
    """One process lifetime: tune (or store-hit) + first search result.

    ``start_to_first_result_s`` spans plan acquisition through the
    first materialised output — the window the plan store exists to
    shrink.  Cold (empty store) pays the search and every XLA compile;
    warm replays the stored config + serialized executables.
    """
    import numpy as np

    from repro.tune import plan_store_stats, tune_plan, tune_stats

    mod = _module(WARM)
    q, p = _data(WARM, seed=7)
    t0 = time.perf_counter()
    res = tune_plan(mod, q, p, trials=WARM_TRIALS, reps=1)
    import jax
    v, i = jax.block_until_ready(res.plan.execute(q, p))
    wall = time.perf_counter() - t0
    digest = hashlib.sha256(
        np.asarray(v).tobytes() + np.asarray(i).tobytes()).hexdigest()
    return {
        "start_to_first_result_s": round(wall, 4),
        "trials": res.trials,
        "from_store": res.from_store,
        "tune": tune_stats(),
        "store": plan_store_stats(),
        "result_digest": digest,
    }


def _spawn_child(store_dir: str) -> dict:
    # JAX's persistent compilation cache stays off in both children: a
    # cache hit would make "cold" measure a lookup, and warm must owe its
    # start-up to the plan store alone
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep +
               os.environ.get("PYTHONPATH", ""),
               REPRO_PLAN_STORE=store_dir,
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_tune", "--run-child"],
        env=env, capture_output=True, text=True, timeout=1800, cwd=ROOT)
    for line in out.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(f"tune child produced no result:\n"
                       f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")


def _cold_vs_warm() -> dict:
    store_dir = tempfile.mkdtemp(prefix="bench-tune-store-")
    cold = _spawn_child(store_dir)
    warm = _spawn_child(store_dir)

    assert cold["trials"] > 0 and not cold["from_store"]
    assert warm["trials"] == 0 and warm["from_store"], \
        "warm process re-ran the search"
    assert warm["store"]["exec_hits"] == 2, \
        "warm process did not adopt the stored executables (recompiled)"
    assert warm["store"]["exec_fallbacks"] == 0, \
        "adopted executables fell back to the lazy-jit (compiling) path"
    assert warm["result_digest"] == cold["result_digest"], \
        "warm-started results are not bit-identical to the tuned run"

    speedup = (cold["start_to_first_result_s"] /
               max(warm["start_to_first_result_s"], 1e-9))
    return {"workload": WARM, "cold": cold, "warm": warm,
            "warm_start_speedup": round(speedup, 2)}


def run() -> dict:
    banner("Tune — searched plans vs heuristics + plan-store warm start")
    # the children first: an accelerator belongs to one process at a
    # time, and the in-process sweep would hold it
    cw = _cold_vs_warm()
    sweep = _sweep()
    print(table(sweep, cols=["shape", "n", "heuristic_ms", "tuned_ms",
                             "speedup", "trials"]))
    best = max(r["speedup"] for r in sweep)

    print(f"\ncold start : {cw['cold']['start_to_first_result_s']:.3f}s "
          f"({cw['cold']['trials']} trials)")
    print(f"warm start : {cw['warm']['start_to_first_result_s']:.3f}s "
          f"(0 trials, executables adopted)")
    print(f"warm-start speedup: {cw['warm_start_speedup']:.2f}x, "
          f"best tuned speedup: {best:.2f}x")

    gate, warm_gate = _gate(), _warm_gate()
    payload = {
        "gate": gate, "warm_gate": warm_gate,
        "trials_per_shape": TRIALS, "reps": REPS,
        "sweep": sweep, "best_tuned_speedup": best,
        "warm_start": cw,
    }
    save_bench_json("tune", payload)

    if gate > 0:
        assert best >= gate, (
            f"tuned plans only reached {best:.2f}x the heuristic on the "
            f"swept shapes (gate: >= {gate}x on at least one); see "
            f"BENCH_tune.json")
    if warm_gate > 0:
        assert cw["warm_start_speedup"] >= warm_gate, (
            f"plan-store warm start only {cw['warm_start_speedup']:.2f}x "
            f"faster to first result (gate: >= {warm_gate}x); see "
            f"BENCH_tune.json")
    return payload


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    if "--run-child" in argv:
        print(_MARK + json.dumps(_child()))
        return 0
    run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
