#!/usr/bin/env python3
"""Bring-up smoke of the C4CAM compile -> plan -> serve path on a TPU.

Drives the main path once through the entry points a user calls, at
deployment sizes, on random data made from ``--seed``, and checks every
answer against a reference written here:

* A — flat float KNN at the SIFT1M shape (1,000,000 x 128 float32, L2,
  k=10): ``compile_fn``, served by ``CamSearchServer`` to 4 client
  threads x 8 requests x 128 queries; recall@10 against a blocked jnp
  search at ``precision=HIGHEST``.
* B — packed binary search (1,000,000 x 256-bit codes, hamming, k=10),
  served the same way; indices bit-identical to a numpy brute force.
* C — the A and B programs on the Pallas backend through
  ``plan.execute``: B bit-identical to the served jnp plan, A at the
  same recall bar.
* D — HDC classifier (196 features, 10 classes, 10,000-wide
  hypervectors): fit, compile, one retrain epoch pushed through
  ``update_gallery`` on a live server, then predict; the encode runs the
  Pallas kernel and predictions equal ``predict_reference``.

``--four-chips`` runs only the sharded path instead: the phase-A gallery
in a 4-shard served plan against a one-chip plan in the same process.

There is no fallback.  The script refuses a device that is not a known
TPU, a server that served any batch degraded or saw a backend error, and
a Pallas kernel run in interpret mode.  The seconds it prints are
one-off smoke timings, not benchmark metrics.  The last line of a run
that passed is a JSON object naming the device; any failure exits
non-zero without it.

    python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: device kinds this smoke knows (``jax.devices()[0].device_kind``)
KNOWN_KINDS = {"TPU v5 lite": "TPU v5e"}
K = 10
RECALL_BAR = 0.99
CLIENTS, REQUESTS, ROWS = 4, 8, 128      # 4 threads x 8 requests x 128 rows
QUERIES = CLIENTS * REQUESTS * ROWS
FLAT_N, FLAT_DIM = 1_000_000, 128        # SIFT1M shape
CODE_N, CODE_BITS = 1_000_000, 256
HDC_DIM = 10_000
HDC_FIT = 32                             # one-shot samples before retraining
BRUTE_QUERIES = 512                      # phase-B queries checked in numpy


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def knn_kernel(queries, gallery):
    diff = queries.unsqueeze(1).sub(gallery)     # (Q,1,D) - (N,D)
    return diff.norm(p=2, dim=-1).topk(K, largest=False)


def compile_flat(n: int, dim: int, *, backend: str = "jnp",
                 shards: int | None = None):
    """The phase-A program through the public compile entry point."""
    from repro.core import ArchSpec, CamType, compile_fn
    example = [(ROWS, dim), (n, dim)]        # traced for shapes only
    arch = ArchSpec(rows=256, cols=256, cam_type=CamType.MCAM,
                    bits_per_cell=8)
    return compile_fn(knn_kernel, example, arch, cam_type=CamType.MCAM,
                      value_bits=8, backend=backend, shards=shards)


def hamming_module(n: int, bits: int):
    """The phase-B program: a fused ``cim.similarity`` (hamming, k=10)
    partitioned onto 256 x 256 binary TCAM subarrays."""
    from repro.core import (ArchSpec, Builder, Module, PassManager,
                            TensorType)
    from repro.core.cim_dialect import (make_acquire, make_execute,
                                        make_release, make_similarity,
                                        make_yield)
    from repro.core.passes import CompulsoryPartition

    mod = Module("codes", [TensorType((ROWS, bits)), TensorType((n, bits))])
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, list(mod.arguments),
                       [TensorType((ROWS, K)), TensorType((ROWS, K), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, mod.arguments[0], mod.arguments[1],
                          metric="hamming", k=K, largest=False,
                          extra_attrs={"value_bits": 1})
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition())
    return pm.run(mod, {"arch": ArchSpec(rows=256, cols=256)})


# -- references ---------------------------------------------------------------


def exact_l2_topk(queries, gallery, k: int, block: int = 65536,
                  qblock: int = 512) -> np.ndarray:
    """Exact L2 top-k on the device: float32 at ``precision=HIGHEST``,
    blocked over the gallery with a running top-k (ties to the lower
    index)."""
    import jax
    import jax.numpy as jnp

    n, d = gallery.shape
    block = min(block, n)
    nb = -(-n // block)
    g = jnp.pad(jnp.asarray(gallery), ((0, nb * block - n), (0, 0)))
    g = g.reshape(nb, block, d)
    offs = jnp.arange(nb, dtype=jnp.int32) * block

    @jax.jit       # the gallery is an argument: a closure would embed it
    def search(q, g, offs):
        qq = jnp.sum(q * q, axis=1, keepdims=True)

        def step(carry, xs):
            gb, off = xs
            dist = qq + jnp.sum(gb * gb, axis=1)[None, :] - 2.0 * jnp.matmul(
                q, gb.T, precision=jax.lax.Precision.HIGHEST)
            idx = off + jnp.arange(block, dtype=jnp.int32)
            dist = jnp.where(idx[None, :] < n, dist, jnp.inf)
            bv, bi = jax.lax.top_k(-dist, k)
            cv = jnp.concatenate([carry[0], bv], axis=1)
            ci = jnp.concatenate([carry[1], idx[bi]], axis=1)
            v, sel = jax.lax.top_k(cv, k)
            return (v, jnp.take_along_axis(ci, sel, axis=1)), None

        init = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
                jnp.zeros((q.shape[0], k), jnp.int32))
        (_, i), _ = jax.lax.scan(step, init, (g, offs))
        return i

    return np.concatenate([
        np.asarray(search(jnp.asarray(queries[s:s + qblock]), g, offs))
        for s in range(0, len(queries), qblock)])


def hamming_topk_numpy(qcodes: np.ndarray, codes: np.ndarray, k: int,
                       qblock: int = 64) -> np.ndarray:
    """Brute-force hamming top-k in numpy, ties to the lower index.
    ``|q| + |p| - 2 q.p`` over {0, 1} cells is exact in float32."""
    g = codes.astype(np.float32)
    gsum = g.sum(axis=1)
    out = np.empty((len(qcodes), k), np.int64)
    for s in range(0, len(qcodes), qblock):
        q = qcodes[s:s + qblock].astype(np.float32)
        d = q.sum(axis=1, keepdims=True) + gsum[None, :] - 2.0 * (q @ g.T)
        kth = np.partition(d, k - 1, axis=1)[:, k - 1]
        for r in range(d.shape[0]):
            cand = np.flatnonzero(d[r] <= kth[r])
            out[s + r] = cand[np.lexsort((cand, d[r, cand]))[:k]]
    return out


def recall_at_k(got: np.ndarray, ref: np.ndarray) -> float:
    return float((got[:, :, None] == ref[:, None, :]).any(-1).mean())


# -- serving -------------------------------------------------------------------


def serve(server, queries: np.ndarray):
    """CLIENTS threads x REQUESTS requests, the queries split evenly
    between them; returns the served indices in query order and the
    window's wall seconds."""
    out = [None] * (CLIENTS * REQUESTS)
    rows = len(queries) // len(out)
    errors = []

    def client(c):
        try:
            for r in range(REQUESTS):
                j = c * REQUESTS + r
                _, idx = server.search(queries[j * rows:(j + 1) * rows])
                out[j] = np.asarray(idx)
        except Exception as e:              # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(CLIENTS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return np.concatenate(out), wall


def check_server(server) -> None:
    """No batch was served by a fallback level and no backend failed."""
    for view, rec in (("health", server.health()),
                      ("snapshot", server.snapshot())):
        if rec["degraded_batches"] or rec["backend_errors"]:
            raise AssertionError(
                f"server {view}: degraded_batches={rec['degraded_batches']} "
                f"backend_errors={rec['backend_errors']}")


def plan_fields(plan) -> dict:
    return {"backend": plan.backend, "packed": plan.packed,
            "shards": plan.shards}


# -- phases ------------------------------------------------------------------


def flat_data(rng, n: int, dim: int, n_queries: int = QUERIES):
    gallery = rng.standard_normal((n, dim), dtype=np.float32)
    queries = rng.standard_normal((n_queries, dim), dtype=np.float32)
    return gallery, queries


def code_data(rng, n: int, bits: int, n_queries: int = QUERIES):
    def draw(m):
        return np.unpackbits(rng.integers(0, 256, (m, bits // 8),
                                          dtype=np.uint8), axis=1)
    return draw(n), draw(n_queries)


def phase_flat(gallery, queries) -> dict:
    """A: compile_fn + CamSearchServer, recall against exact_l2_topk."""
    from repro.serving import CamSearchServer

    t0 = time.perf_counter()
    prog = compile_flat(*gallery.shape)
    with CamSearchServer(prog, gallery, max_wait_ms=2.0) as srv:
        srv.search(queries[:ROWS])            # prepare + compile
        compile_s = time.perf_counter() - t0
        idx, wall = serve(srv, queries)
        check_server(srv)
    ref = exact_l2_topk(queries, gallery, K)
    rec = recall_at_k(idx, ref)
    log("A", compile_s=compile_s, wall_s=wall, queries=len(queries),
        recall_at_10=rec, **plan_fields(prog.engine_plan))
    if rec < RECALL_BAR:
        raise AssertionError(f"phase A recall@10 {rec} < {RECALL_BAR}")
    return {"ref": ref, "idx": idx}


def phase_codes(codes, qcodes, brute_queries: int = BRUTE_QUERIES) -> dict:
    """B: packed hamming plan served, checked against numpy brute force."""
    from repro.core import get_plan
    from repro.serving import CamSearchServer

    t0 = time.perf_counter()
    plan = get_plan(hamming_module(*codes.shape))
    if not plan.packed:
        raise AssertionError("hamming plan did not take the packed path")
    with CamSearchServer(plan, codes, max_wait_ms=2.0) as srv:
        srv.search(qcodes[:ROWS])
        compile_s = time.perf_counter() - t0
        idx, wall = serve(srv, qcodes)
        check_server(srv)
    step = max(1, len(qcodes) // brute_queries)
    ref = hamming_topk_numpy(qcodes[::step], codes, K)
    match = int((idx[::step] == ref).all(axis=1).sum())
    log("B", compile_s=compile_s, wall_s=wall, queries=len(qcodes),
        brute_checked=len(ref), rows_identical=match, **plan_fields(plan))
    if match != len(ref):
        raise AssertionError(f"phase B: {len(ref) - match} of {len(ref)} "
                             f"queries differ from the numpy brute force")
    return {"idx": idx}


def _execute_timed(plan, queries, *stored):
    t0 = time.perf_counter()
    _, idx = plan.execute(queries, *stored)
    idx = np.asarray(idx)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(plan.execute(queries, *stored)[1])
    return idx, compile_s, time.perf_counter() - t0


def phase_pallas(flat, codes, a: dict, b: dict) -> None:
    """C: the A and B programs on the Pallas backend via plan.execute."""
    import jax.numpy as jnp

    from repro.core import get_plan

    gallery, queries = flat
    plan = compile_flat(*gallery.shape, backend="pallas").engine_plan
    idx, compile_s, wall = _execute_timed(plan, queries,
                                          jnp.asarray(gallery))
    rec = recall_at_k(idx, a["ref"])
    log("C/A", compile_s=compile_s, wall_s=wall, queries=len(queries),
        recall_at_10=rec, **plan_fields(plan))
    if rec < RECALL_BAR:
        raise AssertionError(f"phase C/A recall@10 {rec} < {RECALL_BAR}")

    cgal, cq = codes
    plan = get_plan(hamming_module(*cgal.shape), backend="pallas")
    idx, compile_s, wall = _execute_timed(plan, cq, jnp.asarray(cgal))
    match = int((idx == b["idx"]).all(axis=1).sum())
    log("C/B", compile_s=compile_s, wall_s=wall, queries=len(cq),
        rows_identical_to_jnp=match, **plan_fields(plan))
    if match != len(cq):
        raise AssertionError(f"phase C/B: {len(cq) - match} queries differ "
                             f"from the served jnp plan")


def phase_hdc(seed: int, dim: int) -> str:
    """D: HDC fit -> compile -> retrain on a live server -> predict.
    Returns the encode path the classifier took."""
    from repro.data import hdc_mnist_dataset
    from repro.hdc import HdcClassifier
    from repro.hdc.encoding import _kernel_choice
    from repro.serving import CamSearchServer

    xtr, ytr, xte, _ = hdc_mnist_dataset()
    t0 = time.perf_counter()
    clf = HdcClassifier(xtr.shape[1], 10, dim=dim, seed=seed)
    enc = clf.encode(xtr)
    # one-shot from a few samples leaves mistakes for the retrain epoch
    # to correct, so the live server's gallery really changes
    clf.fit(xtr[:HDC_FIT], ytr[:HDC_FIT], encoded=enc[:HDC_FIT]).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with CamSearchServer(clf.plan, clf.gallery, max_wait_ms=2.0) as srv:
        acc, pushed = clf.retrain_epoch(xtr, ytr, encoded=enc, server=srv)
        served = np.asarray(srv.search(clf.encode(xte))[1])[:, 0]
        check_server(srv)
        updates = srv.snapshot()["gallery_updates"]
    pred = clf.predict(xte)
    wall = time.perf_counter() - t0
    want = clf.predict_reference(xte)
    same = int(((pred == want) & (served == want)).sum())
    log("D", compile_s=compile_s, wall_s=wall, encode=_kernel_choice(),
        train_acc_before_retrain=acc, rows_pushed=pushed,
        gallery_updates=updates,
        predictions_equal_reference=f"{same}/{len(pred)}",
        **plan_fields(clf.plan))
    if not pushed or not updates:
        raise AssertionError("phase D: the retrain epoch pushed no rows")
    if same != len(pred):
        raise AssertionError(f"phase D: {len(pred) - same} served or plan "
                             f"predictions differ from predict_reference")
    return _kernel_choice()


def phase_sharded(gallery, queries, shards: int = 4) -> None:
    """The phase-A gallery on a ``shards``-way plan, served, against a
    one-chip plan built in the same process."""
    import jax
    import jax.numpy as jnp

    from repro.serving import CamSearchServer

    t0 = time.perf_counter()
    prog = compile_flat(*gallery.shape, shards=shards)
    plan = prog.engine_plan
    if not plan.shards == jax.device_count() == shards:
        raise AssertionError(f"plan.shards={plan.shards}, "
                             f"device_count={jax.device_count()}, "
                             f"wanted {shards}")
    g = jnp.asarray(gallery)
    with CamSearchServer(prog, g, max_wait_ms=2.0) as srv:
        srv.search(queries[:ROWS])
        compile_s = time.perf_counter() - t0
        idx, wall = serve(srv, queries)
        check_server(srv)
        leaves = plan._prepared_patterns(srv.gallery)
    devices = set().union(*(x.sharding.device_set for x in leaves))
    log("S4", compile_s=compile_s, wall_s=wall, queries=len(queries),
        prepared_devices=len(devices), **plan_fields(plan))
    if len(devices) != shards:
        raise AssertionError(f"prepared gallery spans {len(devices)} "
                             f"devices, wanted {shards}")

    one = compile_flat(*gallery.shape).engine_plan
    idx1, compile1_s, wall1 = _execute_timed(one, queries, g)
    ref = exact_l2_topk(queries, gallery, K)
    rec4, rec1 = recall_at_k(idx, ref), recall_at_k(idx1, ref)
    same = int((idx == idx1).all(axis=1).sum())
    log("S1", compile_s=compile1_s, wall_s=wall1, recall_at_10=rec1,
        sharded_recall_at_10=rec4,
        rows_identical_to_one_chip=f"{same}/{len(idx)}", **plan_fields(one))
    if same != len(idx) or min(rec4, rec1) < RECALL_BAR:
        raise AssertionError("sharded and one-chip plans disagree or miss "
                             "the recall bar")


# -- entry point ---------------------------------------------------------------


def _record_pallas_calls(calls: list) -> None:
    """Record (kernel name, interpret) for every Pallas call traced from
    here on, so the run can prove it compiled each kernel for the chip."""
    from jax.experimental import pallas as pl

    launch = pl.pallas_call

    def recording(kernel, *args, **kwargs):
        name = getattr(kernel, "func", kernel).__name__
        calls.append((name, bool(kwargs.get("interpret", False))))
        return launch(kernel, *args, **kwargs)

    pl.pallas_call = recording


def _require_compiled(calls: list, kernel: str) -> None:
    interpreted = [name for name, interp in calls if interp]
    if interpreted:
        raise AssertionError(f"Pallas kernels ran in interpret mode: "
                             f"{sorted(set(interpreted))}")
    if not any(name == kernel for name, _ in calls):
        raise AssertionError(f"kernel {kernel} was never compiled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard path (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    from repro.core.envcfg import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" or dev.device_kind not in KNOWN_KINDS:
        print(f"chip_smoke: needs a TPU ({', '.join(KNOWN_KINDS)}); "
              f"JAX found {dev.platform} / {dev.device_kind}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=jax.device_count(), jax=jax.__version__,
        compile_cache=cache_dir)
    rng = np.random.default_rng(args.seed)
    t_all = time.perf_counter()

    if args.four_chips:
        phase_sharded(*flat_data(rng, FLAT_N, FLAT_DIM))
    else:
        from repro.core import clear_plan_cache

        calls: list = []
        _record_pallas_calls(calls)
        flat = flat_data(rng, FLAT_N, FLAT_DIM)
        a = phase_flat(*flat)
        clear_plan_cache()
        codes = code_data(rng, CODE_N, CODE_BITS)
        b = phase_codes(*codes)
        clear_plan_cache()
        phase_pallas(flat, codes, a, b)
        _require_compiled(calls, "_topk_kernel")
        clear_plan_cache()
        calls.clear()
        encode = phase_hdc(args.seed, HDC_DIM)
        if encode != "pallas":
            raise AssertionError(f"HDC encode took {encode!r}, not pallas")
        _require_compiled(calls, "_encode_kernel")
    log("done", total_s=time.perf_counter() - t_all)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
