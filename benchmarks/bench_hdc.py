"""HDC benchmark: incremental ``update_rows`` vs full gallery re-prepare.

The tentpole claim of the mutable-gallery engine: an online-learning
workload that touches a small fraction of a large gallery (HDC
retraining rewrites a handful of class rows; one-shot learners touch a
few exemplars) must not pay a full re-encode + re-pack + re-layout of
every stored row.  This benchmark mutates ``rows_touched`` rows spread
over a few row tiles of a large packed bipolar gallery and times

* **incremental** — ``plan.update_rows(donate=True)``: in-place source
  scatter + touched row tiles re-laid + memo seeded,
* **full**        — the same donated scatter followed by a full
  gallery re-prepare (pattern-memo miss: every row re-encoded,
  re-packed and re-laid).

Both timings run to *servable*: they block until the prepared layout
the next dispatch would use is materialised.  The per-search cost is
recorded separately (identical for both paths — a memo hit).  Results
are checked bit-identical before timing.  Writes
``BENCH_hdc.json``; the gate is the incremental speedup at the large
point: ``REPRO_HDC_GATE=auto`` -> 3.0, any float overrides, ``0``/
``off`` disables.  An informational HDC retraining record (one-shot ->
retrained accuracy on the synthetic MNIST stand-in) rides along.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import ArchSpec, clear_plan_cache, get_plan
from repro.core.envcfg import env_gate
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.ir import Builder, Module, PassManager, TensorType
from repro.core.passes import CompulsoryPartition
from repro.hdc.encoding import random_hypervectors

from .common import banner, save_bench_json, table

#: (n_rows, dim, rows_touched, tiles_touched); first point carries the gate
POINTS = ((10_000, 2048, 100, 4), (4096, 1024, 40, 2))
REPEATS = 9


def _gate() -> float:
    return env_gate("REPRO_HDC_GATE", 3.0)


def _sim_module(m, n, dim, arch):
    mod = Module("hdc_bench", [TensorType((m, dim)), TensorType((n, dim))])
    q, p = mod.arguments
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, [q, p],
                       [TensorType((m, 1)), TensorType((m, 1), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, q, p, metric="dot", k=1, largest=True)
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition())
    return pm.run(mod, {"arch": arch})


def _time(fn) -> float:
    fn()                                    # warmup (compile + prepare)
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _draw_update(rng, n, tile_rows, rows_touched, tiles_touched, dim):
    """Rows clustered in a few tiles — the locality retraining has
    (few classes touched per epoch, class rows adjacent)."""
    tiles = rng.choice(n // tile_rows, size=tiles_touched, replace=False)
    pool = (tiles[:, None] * tile_rows
            + np.arange(tile_rows)[None, :]).reshape(-1)
    pool = pool[pool < n]
    idx = np.sort(rng.choice(pool, size=rows_touched, replace=False))
    return idx, random_hypervectors(rng, rows_touched, dim)


def _bench_updates():
    rng = np.random.default_rng(0)
    rows_out, results = [], {}
    for n, dim, touched, tiles in POINTS:
        clear_plan_cache()
        tile_rows = 128
        arch = ArchSpec(rows=tile_rows, cols=512)
        mod = _sim_module(8, n, dim, arch)
        plan = get_plan(mod)
        assert plan.packed, "bipolar dot should auto-pack"
        q = random_hypervectors(rng, 8, dim)
        g0 = jnp.asarray(random_hypervectors(rng, n, dim))
        plan.execute(q, g0)                 # compile + initial prepare

        # parity before timing: incremental layout == full re-prepare
        idx, new = _draw_update(rng, n, tile_rows, touched, tiles, dim)
        g_inc = plan.update_rows(g0, idx, new)
        v1, i1 = plan.execute(q, g_inc)
        clear_plan_cache()
        check = get_plan(mod)
        v2, i2 = check.execute(q, np.asarray(g_inc))
        assert np.array_equal(np.asarray(i1), np.asarray(i2)) and \
            np.array_equal(np.asarray(v1), np.asarray(v2)), \
            "incremental update diverged from full re-prepare"
        clear_plan_cache()
        plan = get_plan(mod)
        plan.execute(q, g0)

        state = {"g": g0, "step": 0}
        # pre-drawn update stream: the timed region is the update path
        # itself, not the RNG producing the new rows
        updates = [_draw_update(rng, n, tile_rows, touched, tiles, dim)
                   for _ in range(2 * (REPEATS + 1))]

        def next_update():
            idx, new = updates[state["step"] % len(updates)]
            state["step"] += 1
            return idx, new

        def block_prepared(g):
            """Block until the layout the next dispatch serves from is
            materialised (memo hit for inc, full prepare for full)."""
            for leaf in plan._prepared_patterns(g):
                leaf.block_until_ready()

        def incremental():
            idx, new = next_update()
            state["g"] = plan.update_rows(state["g"], idx, new, donate=True)
            block_prepared(state["g"])

        def full():
            from repro.core.engine import _scatter_rows_donated

            idx, new = next_update()
            g2 = _scatter_rows_donated(state["g"], jnp.asarray(idx),
                                       jnp.asarray(new))
            state["g"] = g2                  # fresh array: memo miss
            block_prepared(g2)

        fb0 = plan.row_update_fallbacks
        t_inc = _time(incremental)
        assert plan.row_update_fallbacks == fb0, \
            "incremental path fell back to full re-prepare"
        t_full = _time(full)
        t_search = _time(
            lambda: plan.execute(q, state["g"])[1].block_until_ready())

        speedup = t_full / max(t_inc, 1e-9)
        key = f"n{n}"
        results[key] = {
            "n": n, "dim": dim, "rows_touched": touched,
            "tiles_touched": tiles, "tile_rows": tile_rows,
            "touched_frac": round(touched / n, 4),
            "incremental_ms": round(1e3 * t_inc, 3),
            "full_reprepare_ms": round(1e3 * t_full, 3),
            "search_ms": round(1e3 * t_search, 3),
            "speedup": round(speedup, 2),
        }
        rows_out.append({"n": n, "dim": dim, "touched": touched,
                         "inc_ms": 1e3 * t_inc, "full_ms": 1e3 * t_full,
                         "search_ms": 1e3 * t_search, "speedup": speedup})
    print(table(rows_out))
    return results


def _bench_retrain():
    """Informational: the served workload the update path exists for."""
    from repro.data import hdc_mnist_dataset
    from repro.hdc import HdcClassifier

    train_x, train_y, test_x, test_y = hdc_mnist_dataset()
    clf = HdcClassifier(train_x.shape[1], 10, dim=2048, n_levels=16, seed=0)
    clf.fit(train_x, train_y).compile(ArchSpec(rows=8, cols=128),
                                      batch_hint=128)
    enc_tr = clf.encode(train_x)
    enc_te = clf.encode(test_x)
    acc0 = float((clf.predict(encoded=enc_te) == test_y).mean())
    pushed_total = 0
    for _ in range(6):
        _, pushed = clf.retrain_epoch(train_x, train_y, encoded=enc_tr)
        pushed_total += pushed
    acc1 = float((clf.predict(encoded=enc_te) == test_y).mean())
    print(f"hdc retrain: one-shot {acc0:.3f} -> retrained {acc1:.3f} "
          f"({pushed_total} AM rows pushed incrementally)")
    return {"one_shot_acc": round(acc0, 4), "retrained_acc": round(acc1, 4),
            "rows_pushed": pushed_total,
            "row_update_fallbacks": clf.plan.row_update_fallbacks}


def run():
    banner("HDC — incremental update_rows vs full gallery re-prepare")
    results = _bench_updates()
    retrain = _bench_retrain()

    gate = _gate()
    first = POINTS[0]
    gated = results[f"n{first[0]}"]
    payload = {
        "points": results,
        "retrain": retrain,
        "repeats": REPEATS,
        "gate": gate,
        "gate_point": f"n{first[0]}",
        "speedup": gated["speedup"],
    }
    save_bench_json("hdc", payload)
    if gate:
        assert gated["speedup"] >= gate, (
            f"incremental update_rows only {gated['speedup']:.2f}x over "
            f"full re-prepare (gate: >= {gate}x); see BENCH_hdc.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
