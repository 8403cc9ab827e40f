"""Tracing overhead benchmark: the observability tax, measured.

Runs the same packed-hamming dispatch loop three ways and prices the
``repro.obs`` instrumentation against it:

* **disabled ns/call** — a tight loop over :func:`repro.obs.trace_span`
  with the recorder off: the per-call-site cost every hot path pays
  when nobody is tracing (one attribute read + branch + a shared
  singleton; no allocation).
* **disabled overhead** — that per-call cost times the span call sites
  one dispatch actually crosses, as a fraction of the dispatch time.
  Gate: <= ``REPRO_TRACE_GATE`` percent (``auto`` -> 1.0; tracing you
  are not using must be free).
* **enabled overhead** — best-of wall clock of the loop with the
  recorder on vs off.  Gate: <= 10x ``REPRO_TRACE_GATE`` percent
  (``auto`` -> 10%; recording into the bounded ring is cheap but not
  free).

Writes ``BENCH_trace.json``.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import ArchSpec, Builder, Module, PassManager, TensorType, \
    clear_plan_cache, get_plan
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.envcfg import env_gate
from repro.core.passes import CompulsoryPartition
from repro.obs import trace as _trace

from .common import banner, save_bench_json, table

N_GALLERY = 32_768
DIM = 256
K = 10
M_QUERIES = 64
ITERS = 10          # dispatches per timed sample
REPEATS = 5         # best-of samples per configuration
CALIB_CALLS = 200_000


def _gate() -> float:
    return env_gate("REPRO_TRACE_GATE", 1.0)


def _module(m, n, dim, k, arch):
    mod = Module("trace_bench", [TensorType((m, dim)), TensorType((n, dim))])
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


def _disabled_ns_per_call() -> float:
    """Per-call cost of a disabled trace_span (enter+exit included)."""
    assert not _trace.tracer.enabled
    span = _trace.trace_span
    t0 = time.perf_counter_ns()
    for _ in range(CALIB_CALLS):
        with span("calib"):
            pass
    return (time.perf_counter_ns() - t0) / CALIB_CALLS


def _time_loop(plan, q, g) -> float:
    """Best-of wall clock for ITERS dispatches."""
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            v, i = plan.execute(q, g)
            np.asarray(v), np.asarray(i)
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    banner("Tracing overhead — disabled must be free, enabled cheap")
    rng = np.random.default_rng(0)
    clear_plan_cache()
    was_enabled = _trace.tracer.enabled
    _trace.stop()

    arch = ArchSpec(rows=128, cols=128)
    mod = _module(M_QUERIES, N_GALLERY, DIM, K, arch)
    g = jnp.asarray((rng.random((N_GALLERY, DIM)) > 0.5)
                    .astype(np.float32))
    q = (rng.random((M_QUERIES, DIM)) > 0.5).astype(np.float32)
    plan = get_plan(mod)
    v, i = plan.execute(q, g)                   # compile + prepare
    np.asarray(v), np.asarray(i)

    ns_per_call = _disabled_ns_per_call()
    t_off = _time_loop(plan, q, g)

    _trace.tracer.clear()
    _trace.enable()
    try:
        t_on = _time_loop(plan, q, g)
        # span call sites one dispatch actually crosses (each same-
        # thread span is one B + one E in the ring)
        _trace.tracer.clear()
        vv, ii = plan.execute(q, g)
        np.asarray(vv), np.asarray(ii)
        spans_per_dispatch = max(1, len(_trace.tracer) // 2)
    finally:
        if not was_enabled:
            _trace.stop()
        _trace.tracer.clear()

    t_dispatch_ms = 1e3 * t_off / ITERS
    off_pct = 100.0 * (ns_per_call * spans_per_dispatch) \
        / (1e9 * t_off / ITERS)
    on_pct = max(0.0, 100.0 * (t_on - t_off) / t_off)

    rows = [
        {"config": "disabled", "ns_per_call": ns_per_call,
         "dispatch_ms": t_dispatch_ms, "overhead_pct": off_pct},
        {"config": "enabled", "ns_per_call": float("nan"),
         "dispatch_ms": 1e3 * t_on / ITERS, "overhead_pct": on_pct},
    ]
    print(table(rows))
    print(f"\n{spans_per_dispatch} span call sites per dispatch")

    gate = _gate()
    payload = {
        "workload": {"n_gallery": N_GALLERY, "dim": DIM, "k": K,
                     "m_queries": M_QUERIES, "iters": ITERS,
                     "metric": "hamming", "packed": bool(plan.packed)},
        "disabled_ns_per_call": round(ns_per_call, 1),
        "spans_per_dispatch": spans_per_dispatch,
        "dispatch_ms_disabled": round(t_dispatch_ms, 3),
        "dispatch_ms_enabled": round(1e3 * t_on / ITERS, 3),
        "overhead_disabled_pct": round(off_pct, 4),
        "overhead_enabled_pct": round(on_pct, 3),
        "repeats": REPEATS,
        "gate_pct": gate,
    }
    save_bench_json("trace", payload)
    if gate:
        assert off_pct <= gate, (
            f"disabled tracing costs {off_pct:.3f}% of a dispatch "
            f"({ns_per_call:.0f} ns/call x {spans_per_dispatch} call "
            f"sites; gate: <= {gate}%); see BENCH_trace.json")
        assert on_pct <= 10 * gate, (
            f"enabled tracing costs {on_pct:.1f}% of a dispatch "
            f"(gate: <= {10 * gate}%); see BENCH_trace.json")
    return payload


if __name__ == "__main__":
    from repro.core.envcfg import enable_compile_cache
    enable_compile_cache()
    run()
