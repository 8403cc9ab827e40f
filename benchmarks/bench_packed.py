"""Packed binary/ternary search benchmark: XOR+popcount vs float hamming.

Runs the same compiled hamming search plan two ways at a CAM-realistic
geometry (128x128 subarrays, binary cells, dim >= 1024):

* **unpacked** — the float path (`pack=False`): {0,1} cells as float32,
  mismatch counts via elementwise compare+sum — 32x the memory traffic
  the data needs.
* **packed**   — the bit-packed path (`pack=True`, the default for
  binary metrics): uint32 lanes, ``popcount(q ^ p)`` — bit-identical
  results (asserted here), 1/32nd the resident gallery.

A ternary (TCAM wildcard) packed plan is timed at the same geometry for
the record.  Writes ``BENCH_packed.json``; the gate is the packed
speedup over the unpacked plan at the dim >= 1024 point:
``REPRO_PACKED_GATE=auto`` -> 4.0 (the match loop is bandwidth-bound,
so the floor is host-invariant), any float overrides, ``0``/``off``
disables.
"""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.core import (ArchSpec, Builder, Module, PassManager, TensorType,
                        clear_plan_cache, get_plan)
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.envcfg import env_gate
from repro.core.passes import CompulsoryPartition

from .common import banner, save_bench_json, table

#: (dim, n_gallery, m_queries); the first point carries the gate
POINTS = ((1024, 4096, 128), (256, 2048, 128))
K = 10
REPEATS = 5


def _hamming_module(m, n, dim, k, arch, care=False):
    """Fused (optionally ternary) hamming program through the partition
    pass — binary cells, so value_bits=1 (one CAM cell per element)."""
    args = [TensorType((m, dim)), TensorType((n, dim))]
    if care:
        args.append(TensorType((n, dim), "i8"))
    mod = Module("ham", args)
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, list(mod.arguments),
                       [TensorType((m, k)), TensorType((m, k), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, mod.arguments[0], mod.arguments[1],
                          metric="hamming", k=k, largest=False,
                          care=mod.arguments[2] if care else None,
                          extra_attrs={"value_bits": 1})
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition(unroll_limit=64))
    return pm.run(mod, {"arch": arch})


def _time_plan(plan, *inputs) -> float:
    """Best-of-REPEATS wall-clock for one full execute (host-synced)."""
    v, i = plan.execute(*inputs)                # compile + prepare (warmup)
    np.asarray(v), np.asarray(i)
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        v, i = plan.execute(*inputs)
        np.asarray(v), np.asarray(i)
        best = min(best, time.perf_counter() - t0)
    return best


def _gate() -> float:
    return env_gate("REPRO_PACKED_GATE", 4.0)


def run():
    banner("Packed search — XOR+popcount vs float hamming plans")
    rng = np.random.default_rng(0)
    arch = ArchSpec(rows=128, cols=128)
    rows, results = [], {}
    for dim, n, m in POINTS:
        mod = _hamming_module(m, n, dim, K, arch)
        clear_plan_cache()
        unpacked = get_plan(mod, pack=False)
        packed = get_plan(mod, pack=True)
        q = (rng.random((m, dim)) > 0.5).astype(np.float32)
        g = jnp.asarray((rng.random((n, dim)) > 0.5).astype(np.float32))

        # the gate is only meaningful if the paths agree bit-for-bit
        pv, pi = packed.execute(q, g)
        uv, ui = unpacked.execute(q, g)
        assert np.array_equal(np.asarray(pv), np.asarray(uv)) and \
            np.array_equal(np.asarray(pi), np.asarray(ui)), \
            "packed result diverged from the unpacked hamming plan"

        t_unpacked = _time_plan(unpacked, q, g)
        t_packed = _time_plan(packed, q, g)

        tmod = _hamming_module(m, n, dim, K, arch, care=True)
        ternary = get_plan(tmod)
        care = jnp.asarray((rng.random((n, dim)) > 0.25).astype(np.int8))
        t_ternary = _time_plan(ternary, q, g, care)

        speedup = t_unpacked / max(t_packed, 1e-9)
        results[f"dim{dim}"] = {
            "dim": dim, "n_gallery": n, "m_queries": m, "k": K,
            "unpacked_ms": round(1e3 * t_unpacked, 2),
            "packed_ms": round(1e3 * t_packed, 2),
            "ternary_packed_ms": round(1e3 * t_ternary, 2),
            "speedup": round(speedup, 2),
        }
        rows.append({"dim": dim, "unpacked_ms": 1e3 * t_unpacked,
                     "packed_ms": 1e3 * t_packed,
                     "ternary_ms": 1e3 * t_ternary, "speedup": speedup})
    print(table(rows))

    gate = _gate()
    gated = results[f"dim{POINTS[0][0]}"]
    payload = {
        "points": results,
        "repeats": REPEATS,
        "gate": gate,
        "gate_point": f"dim{POINTS[0][0]}",
        "speedup": gated["speedup"],
    }
    save_bench_json("packed", payload)
    if gate:
        assert gated["speedup"] >= gate, (
            f"packed plan only {gated['speedup']:.2f}x over the unpacked "
            f"hamming plan at dim={POINTS[0][0]} (gate: >= {gate}x); "
            f"see BENCH_packed.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
