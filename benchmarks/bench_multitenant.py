"""Multi-tenant gateway benchmark: isolation + replica failover.

Three experiments over one small compiled KNN plan (the gateway is the
system under test, not the kernel):

* **aggregate** — three tenants drive the gateway concurrently vs the
  same three workloads run back-to-back on solo servers; records the
  concurrent/sequential throughput ratio (shared plan + interleaved
  batching should keep it near or above 1).
* **isolation** — the acceptance experiment.  A victim tenant's p95 is
  measured solo, then again while a hot tenant floods (a) the gateway,
  where the hot tenant is rate-limited and shed by *its own* admission
  budget, and (b) a naive shared ``CamSearchServer`` with no admission
  layer, where the flood queues ahead of the victim.  Gate: gateway
  victim p95 <= gate x solo **and** naive victim p95 > gate x solo —
  the gateway must deliver the isolation the bare server demonstrably
  lacks.
* **failover** — one tenant on two replicas; concurrent bit-checking
  clients; one replica is killed mid-traffic.  Every request must
  complete bit-identically to the plan oracle (zero failures), the
  gateway must record failovers, and the killed replica must be
  drained, rebuilt onto a fresh device group, and readmitted by the
  maintenance loop before the run ends.

Writes ``BENCH_multitenant.json``.  Gate ``REPRO_MULTITENANT_GATE``
(auto -> 2.0, ``0``/``off`` disables) is the isolation factor above.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.core import ArchSpec, compile_fn
from repro.core.envcfg import env_gate
from repro.serving import AdmissionError, CamSearchServer, \
    CamServingGateway, TenantUnavailable

from .common import banner, save_bench_json, table

N, DIM, K = 512, 64, 5
ROWS = 8                   # query rows per request
SEED = 7


def _gate() -> float:
    return env_gate("REPRO_MULTITENANT_GATE", 2.0)


def _knn(q, gallery):
    d = q.unsqueeze(1).sub(gallery).norm(p=2, dim=-1)
    return d.topk(K, largest=False)


def _compile(rng):
    gal = rng.standard_normal((N, DIM)).astype(np.float32)
    prog = compile_fn(_knn, [np.zeros((32, DIM), np.float32), gal],
                      ArchSpec(rows=64, cols=64))
    return prog, gal


def _p95(lat):
    lat = sorted(lat)
    return 1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.95))]


def _drive(search, queries, reps):
    """Run ``reps`` sequential requests, returning per-request wait
    latencies (seconds)."""
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        search(queries)
        out.append(time.perf_counter() - t0)
    return out


# -- experiment 1: aggregate throughput ------------------------------------

def _bench_aggregate(prog, gal, rng):
    tenants = ["t0", "t1", "t2"]
    reps, q = 40, rng.standard_normal((ROWS, DIM)).astype(np.float32)

    solo_t0 = time.perf_counter()
    for _ in tenants:
        with CamSearchServer(prog, gal) as srv:
            _drive(srv.search, q, reps)
    solo_s = time.perf_counter() - solo_t0

    gw = CamServingGateway(maint_ms=0.0)
    gw.register_tenant(tenants[0], prog, gal)
    for t in tenants[1:]:
        gw.register_tenant(t, share_with=tenants[0])
    conc_t0 = time.perf_counter()
    threads = [threading.Thread(
        target=lambda t=t: _drive(lambda x: gw.search(t, x), q, reps))
        for t in tenants]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    conc_s = time.perf_counter() - conc_t0
    gw.stop()

    total_q = len(tenants) * reps * ROWS
    rec = {"tenants": len(tenants), "requests_per_tenant": reps,
           "sequential_s": round(solo_s, 3),
           "concurrent_s": round(conc_s, 3),
           "concurrent_qps": round(total_q / conc_s, 1),
           "throughput_ratio": round(solo_s / conc_s, 2)}
    print(table([rec]))
    return rec


# -- experiment 2: hot-tenant isolation ------------------------------------

def _flood(submit, stop_evt, counters, inflight=32):
    """Hot-tenant flood until told to stop.

    Bounded in-flight (not fire-and-forget): an unbounded flood makes
    the *naive* victim latency a function of run length, not of the
    server's scheduling — with a fixed backlog the measured isolation
    factor is stable.
    """
    pending = []
    while not stop_evt.is_set():
        try:
            h = submit()
            counters["accepted"] += 1
            if h is not None:
                pending.append(h)
        except (AdmissionError, TenantUnavailable):
            counters["rejected"] += 1
            time.sleep(1e-3)        # rejected: back off, don't busy-spin
        except RuntimeError:
            break
        while len(pending) >= inflight:
            try:
                pending.pop(0).wait(30)
            except TimeoutError:
                pass
    for h in pending:
        try:
            h.wait(30)
        except TimeoutError:
            pass


def _bench_isolation(prog, gal, rng):
    gate = _gate()
    vq = rng.standard_normal((ROWS, DIM)).astype(np.float32)
    hq = rng.standard_normal((2 * ROWS, DIM)).astype(np.float32)
    reps = 60

    # victim alone through the gateway: the baseline p95
    gw = CamServingGateway(maint_ms=0.0)
    gw.register_tenant("victim", prog, gal)
    solo = _drive(lambda x: gw.search("victim", x), vq, reps)
    gw.stop()

    # victim + admission-controlled hot tenant on the SAME replica set
    gw = CamServingGateway(maint_ms=0.0)
    gw.register_tenant("victim", prog, gal)
    gw.register_tenant("hot", share_with="victim",
                       rate=4.0 * ROWS, burst=2 * ROWS,
                       queue_limit=4, max_outstanding=2)
    stop_evt, counters = threading.Event(), {"accepted": 0, "rejected": 0}
    flooders = [threading.Thread(
        target=_flood, args=(lambda: gw.submit("hot", hq), stop_evt,
                             counters)) for _ in range(2)]
    for f in flooders:
        f.start()
    gated = _drive(lambda x: gw.search("victim", x), vq, reps)
    stop_evt.set()
    for f in flooders:
        f.join()
    gw.stop()

    # the counterfactual: one bare shared server, no admission layer —
    # the hot flood queues ahead of the victim
    srv = CamSearchServer(prog, gal).start()
    stop_evt2 = threading.Event()
    naive_counters = {"accepted": 0, "rejected": 0}
    flooders = [threading.Thread(
        target=_flood, args=(lambda: srv.submit(hq), stop_evt2,
                             naive_counters)) for _ in range(2)]
    for f in flooders:
        f.start()
    naive = _drive(lambda x: srv.search(x), vq, reps)
    stop_evt2.set()
    for f in flooders:
        f.join()
    srv.stop()

    rec = {"solo_p95_ms": round(_p95(solo), 2),
           "gateway_p95_ms": round(_p95(gated), 2),
           "naive_shared_p95_ms": round(_p95(naive), 2),
           "gateway_factor": round(_p95(gated) / _p95(solo), 2),
           "naive_factor": round(_p95(naive) / _p95(solo), 2),
           "hot_accepted": counters["accepted"],
           "hot_rejected": counters["rejected"],
           "gate": gate}
    print(table([rec]))
    if gate > 0:
        assert rec["gateway_factor"] <= gate, (
            f"victim p95 through the gateway is "
            f"{rec['gateway_factor']}x solo (gate: <= {gate}x) — "
            f"admission control failed to isolate the hot tenant")
        assert rec["naive_factor"] > gate, (
            f"naive shared server victim p95 only {rec['naive_factor']}x "
            f"solo — the flood is too weak to demonstrate isolation")
    return rec


# -- experiment 3: replica-kill failover -----------------------------------

def _bench_failover(prog, gal, rng):
    plan = prog.engine_plan
    gw = CamServingGateway(maint_ms=10.0)
    gw.register_tenant("ten", prog, gal, replicas=2, unhealthy_k=2)
    q_blocks = [rng.standard_normal((ROWS, DIM)).astype(np.float32)
                for _ in range(4)]
    oracles = [np.asarray(plan.execute(q, gal)[1]) for q in q_blocks]

    reps, kill_after = 50, 12
    errors, mismatches = [], []
    lat = {"before": [], "after": []}
    barrier = threading.Barrier(4 + 1)
    killed_evt = threading.Event()

    def client(cid):
        barrier.wait()
        for r in range(reps):
            t0 = time.perf_counter()
            try:
                _, idx = gw.search("ten", q_blocks[cid], timeout=60)
            except Exception as e:              # noqa: BLE001 — recorded
                errors.append(repr(e))
                continue
            dt = time.perf_counter() - t0
            lat["after" if killed_evt.is_set() else "before"].append(dt)
            if not np.array_equal(np.asarray(idx), oracles[cid]):
                mismatches.append((cid, r))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(4)]
    for t in threads:
        t.start()
    barrier.wait()
    # let traffic establish, then take down a device group mid-flight
    time.sleep(kill_after * 0.01)
    gw.kill_replica("ten", 0)
    killed_evt.set()
    for t in threads:
        t.join()

    # the maintenance loop must drain + rebuild + readmit the replica
    healed = False
    for _ in range(500):
        reps_v = gw.health()["tenants"]["ten"]["replicas"]["replicas"]
        if all(r["state"] == "serving" for r in reps_v) and \
                any(r["rebuilds"] > 0 for r in reps_v):
            healed = True
            break
        time.sleep(0.01)
    h = gw.health()["tenants"]["ten"]
    post_v, post_i = gw.search("ten", q_blocks[0])
    post_ok = np.array_equal(np.asarray(post_i), oracles[0])
    gw.stop()

    rec = {"clients": 4, "requests": 4 * reps,
           "errors": len(errors), "mismatches": len(mismatches),
           "failovers": h["stats"]["failovers"],
           "healed": healed, "post_heal_bit_identical": bool(post_ok),
           "p95_before_kill_ms":
               round(_p95(lat["before"]), 2) if lat["before"] else None,
           "p95_after_kill_ms":
               round(_p95(lat["after"]), 2) if lat["after"] else None,
           "replicas": [{k: r[k] for k in
                         ("state", "generation", "rebuilds", "heals",
                          "device_group")}
                        for r in h["replicas"]["replicas"]]}
    print(table([{k: v for k, v in rec.items() if k != "replicas"}]))
    assert not errors, f"failover dropped requests: {errors[:3]}"
    assert not mismatches, f"failover broke bit-identity: {mismatches[:3]}"
    assert rec["failovers"] > 0, \
        "kill landed between requests — no failover exercised"
    assert healed, "killed replica was not rebuilt + readmitted"
    assert post_ok, "post-heal result diverged from the oracle"
    return rec


def run():
    rng = np.random.default_rng(SEED)
    prog, gal = _compile(rng)

    banner("multi-tenant aggregate throughput")
    aggregate = _bench_aggregate(prog, gal, rng)
    banner("hot-tenant isolation (gateway vs naive shared server)")
    isolation = _bench_isolation(prog, gal, rng)
    banner("replica-kill failover")
    failover = _bench_failover(prog, gal, rng)

    payload = {
        "benchmark": "multitenant",
        "workload": {"n": N, "dim": DIM, "k": K, "rows_per_request": ROWS},
        "gate": _gate(),
        "aggregate": aggregate,
        "isolation": isolation,
        "failover": failover,
    }
    save_bench_json("multitenant", payload)
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
