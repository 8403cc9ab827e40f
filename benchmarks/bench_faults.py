"""Fault-injection benchmark: accuracy under device faults + resilient serving.

Three experiments, all fully deterministic in the fault model's seed:

* **forest** — the aCAM decision-forest workload under stuck-cell /
  bit-flip faults, unhardened ``RangePlan`` vs ``HardenedPlan``
  (3x replication + checksum-readback healing onto spare rows).
* **hdc** — the packed-hamming HDC associative memory under the same
  fault family (prototype rows replicated, median-score de-dup).
* **serving** — a ``CamSearchServer`` driven through transient backend
  outages: the resilient config (retries + breaker + degraded fallback)
  must complete 100% of non-timed-out requests, the unprotected config
  shows visible failures on the same fault schedule.

An aCAM guard-band side-table records the miss/false-match trade under
sigma-noise (guard widening recovers misses at the cost of extra
matches) — see docs/robustness.md for why guards are *not* part of the
digital-fault accuracy gate.

Writes ``BENCH_faults.json``.  Gate (``REPRO_FAULTS_GATE``, auto ->
0.9, ``0``/``off`` disables): at the sweep point where the unhardened
accuracy drops >= 10 points, hardened accuracy must stay >= gate x
clean accuracy — for *both* workloads — and the resilient server must
complete every non-timed-out request while the unprotected one fails
at least once.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import clear_plan_cache
from repro.core.arch import ArchSpec, CamType
from repro.core.envcfg import env_gate
from repro.faults import FaultModel, HardenedPlan
from repro.forest import CamForestClassifier, random_forest, vote
from repro.hdc import HdcClassifier

from .common import banner, save_bench_json, table

#: fault-rate sweeps (p_stuck; p_flip rides along at p/2).  Forest rows
#: are short conjunctions (one dead cell kills a branch) so they break
#: at rates an HDC hypervector shrugs off — each workload gets the
#: sweep that brackets its own 10-point accuracy cliff.
FOREST_RATES = (0.002, 0.005, 0.01)
HDC_RATES = (0.01, 0.02, 0.05)
REPLICAS = 3
SEED = 1


def _gate() -> float:
    return env_gate("REPRO_FAULTS_GATE", 0.9)


def _model(p: float) -> FaultModel:
    return FaultModel(seed=SEED, p_stuck=p, p_flip=p / 2)


def _sweep_forest():
    rng = np.random.default_rng(0)
    n_trees, depth, dim, m = 48, 5, 24, 256
    trees = random_forest(rng, n_trees=n_trees, dim=dim, depth=depth,
                          n_classes=8, feature_frac=0.5)
    arch = ArchSpec(rows=64, cols=64, cam_type=CamType.ACAM)
    clf = CamForestClassifier(trees, dim=dim).compile(arch, batch_hint=m)
    x = rng.standard_normal((m, dim)).astype(np.float32)
    iv = clf.intervals
    labels = clf.predict_reference(x)
    clean = float((clf.predict(x) == labels).mean())

    points = []
    for p in FOREST_RATES:
        fm = _model(p)
        match_u = np.asarray(clf.plan.execute(x, iv.lo, iv.hi, faults=fm))
        acc_u = float((vote(match_u, iv.leaf_class, iv.n_classes)
                       == labels).mean())
        hp = HardenedPlan(clf.plan, replicas=REPLICAS, spares=256)
        hp.prepare(iv.lo, iv.hi)
        rep = hp.heal(fm)
        match_h = np.asarray(hp.execute(x, faults=fm))
        acc_h = float((vote(match_h, iv.leaf_class, iv.n_classes)
                       == labels).mean())
        points.append({"p": p, "unhardened": acc_u, "hardened": acc_h,
                       "detected": rep.detected, "remapped": rep.remapped,
                       "unrepairable": rep.unrepairable})
    return {"workload": {"n_trees": n_trees, "depth": depth, "dim": dim,
                         "m": m, "rows": iv.n_rows,
                         "replicas": REPLICAS, "spares": 256},
            "clean": clean, "points": points}


def _guard_table(clf, x):
    """Sigma-noise miss/false-match trade for aCAM guard bands."""
    iv = clf.intervals
    clean = np.asarray(clf.plan.execute(x, iv.lo, iv.hi))
    fm = FaultModel(seed=SEED, sigma=0.02)
    rows = []
    for z in (0.0, 2.0, 4.0):
        hp = HardenedPlan(clf.plan, replicas=1, spares=0,
                          guard=fm.suggest_guard(z=z))
        hp.prepare(iv.lo, iv.hi)
        got = np.asarray(hp.execute(x, faults=fm))
        miss = float((clean & ~got).sum() / max(1, clean.sum()))
        false = float((~clean & got).sum() / max(1, (~clean).sum()))
        rows.append({"guard_z": z, "miss_rate": round(miss, 4),
                     "false_match_rate": round(false, 5)})
    return rows


def _sweep_hdc():
    rng = np.random.default_rng(1)
    n_feat, n_classes, dim = 32, 8, 256
    means = rng.standard_normal((n_classes, n_feat))
    def blobs(n):
        y = rng.integers(0, n_classes, n)
        xx = means[y] + 0.45 * rng.standard_normal((n, n_feat))
        return xx.astype(np.float32), y
    xtr, ytr = blobs(512)
    xte, yte = blobs(256)
    clf = HdcClassifier(n_feat, n_classes, dim=dim, n_levels=8,
                        lo=float(xtr.min()), hi=float(xtr.max()), seed=0)
    clf.fit(xtr, ytr)
    clf.compile(batch_hint=64)
    clean = float((clf.predict(xte) == yte).mean())
    enc = clf.encode(xte)

    points = []
    for p in HDC_RATES:
        fm = _model(p)
        _, idx = clf.plan.execute(enc, clf._gallery, faults=fm)
        acc_u = float((np.asarray(idx)[:, 0] == yte).mean())
        hp = HardenedPlan(clf.plan, replicas=REPLICAS, spares=4)
        hp.prepare(clf._gallery)
        rep = hp.heal(fm)
        _, hidx = hp.execute(enc, faults=fm)
        acc_h = float((np.asarray(hidx)[:, 0] == yte).mean())
        points.append({"p": p, "unhardened": acc_u, "hardened": acc_h,
                       "detected": rep.detected, "remapped": rep.remapped,
                       "unrepairable": rep.unrepairable})
    return {"workload": {"n_features": n_feat, "n_classes": n_classes,
                         "dim": dim, "test": len(yte),
                         "replicas": REPLICAS, "spares": 4},
            "clean": clean, "points": points}


class _Outage:
    """Time-windowed backend outage: every dispatch attempt on any
    level raises while an outage window is open."""

    def __init__(self):
        self.until = 0.0
        self.injected = 0

    def open_window(self, seconds: float) -> None:
        self.until = time.perf_counter() + seconds

    def __call__(self, level: str) -> None:
        if time.perf_counter() < self.until:
            self.injected += 1
            raise RuntimeError(f"injected outage ({level})")


def _serve_workload(protected: bool):
    from repro.core import get_plan
    from repro.core.cim_dialect import (make_acquire, make_execute,
                                        make_release, make_similarity,
                                        make_yield)
    from repro.core.ir import Builder, Module, PassManager, TensorType
    from repro.core.passes import CompulsoryPartition
    from repro.serving import CamSearchServer

    rng = np.random.default_rng(2)
    m, n, dim, k = 8, 128, 64, 4
    mod = Module("faults_serve", [TensorType((m, dim)), TensorType((n, dim))])
    q_arg, p_arg = mod.arguments
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, [q_arg, p_arg],
                       [TensorType((m, k)), TensorType((m, k), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, q_arg, p_arg, metric="eucl", k=k,
                          largest=False)
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    PassManager().add(CompulsoryPartition()).run(
        mod, {"arch": ArchSpec(rows=32, cols=64)})
    plan = get_plan(mod)

    gallery = rng.standard_normal((n, dim)).astype(np.float32)
    queries = [rng.standard_normal((2, dim)).astype(np.float32)
               for _ in range(24)]
    outage = _Outage()
    kw = dict(max_wait_ms=1.0, fault_injector=outage)
    if protected:
        kw.update(max_retries=3, retry_backoff_ms=10.0,
                  breaker_threshold=2, breaker_cooldown_ms=30.0)
    else:
        kw.update(max_retries=0, breaker_threshold=0)
    srv = CamSearchServer(plan, gallery, **kw)
    if not protected:
        # the unprotected baseline really is unprotected: no retries,
        # no breaker, and no degraded chain to hide behind
        srv._fallbacks = []
    completed = failed = timed_out = 0
    with srv:
        reqs = []
        for i, q in enumerate(queries):
            if i % 8 == 0:
                outage.open_window(0.008)
            reqs.append(srv.submit(q))
            time.sleep(0.003)
        for r in reqs:
            res = r.wait(timeout=120)
            if res.error is None:
                completed += 1
            elif isinstance(res.error, TimeoutError):
                timed_out += 1
            else:
                failed += 1
        health = srv.health()
    return {"requests": len(queries), "completed": completed,
            "failed": failed, "timed_out": timed_out,
            "injected_faults": outage.injected,
            "breaker_trips": health["breaker"]["trips"],
            "retries": health["retries"],
            "degraded_batches": health["degraded_batches"],
            "status": health["status"]}


def _gate_point(sweep):
    """First sweep point where unhardened accuracy fell >= 10 points."""
    for pt in sweep["points"]:
        if sweep["clean"] - pt["unhardened"] >= 0.10:
            return pt
    return None


def run():
    banner("Fault injection — accuracy under device faults + resilient "
           "serving")
    clear_plan_cache()

    forest = _sweep_forest()
    hdc = _sweep_hdc()
    for name, sweep in (("forest", forest), ("hdc", hdc)):
        rows = [{"workload": name, "p": pt["p"],
                 "clean": sweep["clean"], "unhardened": pt["unhardened"],
                 "hardened": pt["hardened"], "detected": pt["detected"],
                 "remapped": pt["remapped"]} for pt in sweep["points"]]
        print(table(rows))

    # guard-band side table (sigma noise, forest interval rows)
    rng = np.random.default_rng(0)
    trees = random_forest(rng, n_trees=16, dim=16, depth=4, n_classes=4,
                          feature_frac=0.5)
    gclf = CamForestClassifier(trees, dim=16).compile(
        ArchSpec(rows=64, cols=64, cam_type=CamType.ACAM), batch_hint=64)
    gx = rng.standard_normal((64, 16)).astype(np.float32)
    guard_rows = _guard_table(gclf, gx)
    print(table(guard_rows))

    serve_protected = _serve_workload(protected=True)
    serve_unprotected = _serve_workload(protected=False)
    print(table([dict(config="resilient", **serve_protected),
                 dict(config="unprotected", **serve_unprotected)],
                cols=["config", "requests", "completed", "failed",
                      "timed_out", "injected_faults", "retries",
                      "breaker_trips", "degraded_batches"]))

    gate = _gate()
    fpt, hpt = _gate_point(forest), _gate_point(hdc)
    payload = {
        "gate": gate,
        "forest": forest,
        "hdc": hdc,
        "guard_bands": {"sigma": 0.02, "rows": guard_rows},
        "serving": {"resilient": serve_protected,
                    "unprotected": serve_unprotected},
        "gate_points": {
            "forest": None if fpt is None else fpt["p"],
            "hdc": None if hpt is None else hpt["p"],
        },
    }
    save_bench_json("faults", payload)

    if gate:
        for name, sweep, pt in (("forest", forest, fpt), ("hdc", hdc, hpt)):
            assert pt is not None, (
                f"{name}: no sweep point dropped >= 10 accuracy points "
                f"unhardened — the sweep no longer exercises the fault "
                f"cliff; see BENCH_faults.json")
            assert pt["hardened"] >= gate * sweep["clean"], (
                f"{name}: hardened accuracy {pt['hardened']:.3f} at "
                f"p={pt['p']} fell below {gate} x clean "
                f"({sweep['clean']:.3f}); see BENCH_faults.json")
        sp, su = serve_protected, serve_unprotected
        assert sp["completed"] + sp["timed_out"] == sp["requests"], (
            f"resilient server failed {sp['failed']} requests under "
            f"transient faults; see BENCH_faults.json")
        assert su["failed"] > 0, (
            "unprotected server showed no failures — the outage "
            "schedule no longer exercises the fault path")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
