"""Exact Hamming k-nearest-neighbour search over binary codes.

The program, the data made from the seed, the work a search needs, the
plain reference, the control and the comparison for configurations of
this kind.  The
reference and the control import nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST

#: the controls, by name: keyword arguments of ``control``
CONTROLS = {"control": {}}
#: the control a CPU reads
CPU_CONTROL = "control"


def build(cfg: dict):
    """A fused ``cim.similarity`` (hamming, k) partitioned onto the
    configured binary TCAM subarrays, planned by ``get_plan`` with every
    option at its default."""
    from repro.core import (ArchSpec, Builder, Module, PassManager,
                            TensorType, get_plan)
    from repro.core.cim_dialect import (make_acquire, make_execute,
                                        make_release, make_similarity,
                                        make_yield)
    from repro.core.passes import CompulsoryPartition

    rows, n, bits, k = (cfg["query_rows_traced"], cfg["n"], cfg["dim"],
                        int(cfg["k"]))
    mod = Module("codes", [TensorType((rows, bits)), TensorType((n, bits))])
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, list(mod.arguments),
                       [TensorType((rows, k)), TensorType((rows, k), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, mod.arguments[0], mod.arguments[1],
                          metric="hamming", k=k, largest=False,
                          extra_attrs={"value_bits": 1})
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition())
    a = cfg["arch"]
    return get_plan(pm.run(mod, {"arch": ArchSpec(rows=a["rows"],
                                                  cols=a["cols"])}))


def work(cfg: dict, rows: float) -> dict:
    """Operations and bytes one search of ``rows`` query rows needs,
    whatever implements it: the matmul form ``|q| + |p| - 2 q.p`` over
    {0, 1} cells, ``2 * rows * n * dim`` operations at the int8 peak;
    the codes packed at one bit a cell are ``n * dim / 8`` bytes."""
    if cfg["metric"] != "hamming":
        raise ValueError(f"no work count for metric {cfg['metric']!r}")
    n, dim = cfg["n"], cfg["dim"]
    return {"ops": 2.0 * rows * n * dim, "bytes": n * dim / 8.0,
            "peak": "int8_ops_per_s"}


def make_gallery(cfg: dict, key: jax.Array) -> jax.Array:
    """Uniform random {0, 1} codes, drawn on the device in one call."""
    shape = (cfg["n"], cfg["dim"])
    return jax.jit(lambda k: jax.random.bernoulli(k, 0.5, shape)
                   .astype(jnp.uint8))(key)


def make_queries(cfg: dict, rng: np.random.Generator, count: int,
                 rows: int) -> np.ndarray:
    return rng.integers(0, 2, (count, rows, cfg["dim"]), dtype=np.uint8)


def _blocked_topk(queries: np.ndarray, gallery: jax.Array, k: int,
                  lower_first: bool, block: int = 65536, qblock: int = 512):
    """Brute-force Hamming top-k on the device: ``|q| + |p| - 2 q.p``
    over {0, 1} cells, exact in float32 at ``HIGHEST``, blocked over the
    gallery with a running top-k.  ``lower_first`` breaks ties to the
    lower index (the reference); otherwise to the higher one."""
    n, d = gallery.shape
    block = min(block, n)
    nb = -(-n // block)
    g = jnp.pad(gallery, ((0, nb * block - n), (0, 0))).reshape(nb, block, d)
    offs = jnp.arange(nb, dtype=jnp.int32) * block

    @jax.jit
    def search(q, g, offs):
        q = q.astype(jnp.float32)
        qs = jnp.sum(q, axis=1, keepdims=True)

        def step(carry, xs):
            gb, off = xs
            gb = gb.astype(jnp.float32)
            dist = qs + jnp.sum(gb, axis=1)[None, :] - 2.0 * jnp.matmul(
                q, gb.T, precision=_HIGHEST)
            idx = off + jnp.arange(block, dtype=jnp.int32)
            dist = jnp.where(idx[None, :] < n, dist, jnp.inf)
            if not lower_first:                 # ties to the higher index
                dist, idx = dist[:, ::-1], idx[::-1]
            bv, bi = jax.lax.top_k(-dist, k)
            bi = idx[bi]
            if lower_first:
                cv = jnp.concatenate([carry[0], bv], axis=1)
                ci = jnp.concatenate([carry[1], bi], axis=1)
            else:
                cv = jnp.concatenate([bv, carry[0]], axis=1)
                ci = jnp.concatenate([bi, carry[1]], axis=1)
            v, sel = jax.lax.top_k(cv, k)
            return (v, jnp.take_along_axis(ci, sel, axis=1)), None

        init = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
                jnp.zeros((q.shape[0], k), jnp.int32))
        (v, i), _ = jax.lax.scan(step, init, (g, offs))
        return -v, i

    vals, ids = [], []
    for s in range(0, len(queries), qblock):
        v, i = search(jnp.asarray(queries[s:s + qblock]), g, offs)
        vals.append(np.asarray(v))
        ids.append(np.asarray(i))
    return np.concatenate(vals), np.concatenate(ids)


def reference(cfg: dict, gallery: jax.Array, queries: np.ndarray):
    """The plain reference: exact counts, ties to the lower index."""
    return _blocked_topk(queries, gallery, int(cfg["k"]), True)


def control(cfg: dict, gallery: jax.Array, queries: np.ndarray):
    """The reference with one stated guarantee broken: ties go to the
    higher index."""
    return _blocked_topk(queries, gallery, int(cfg["k"]), False)


def compare(cfg: dict, gallery: jax.Array, queries: np.ndarray,
            values: np.ndarray, ids: np.ndarray, ref) -> dict:
    """``bad_ids``: rows with an index outside the gallery or repeated;
    ``rows_differ``: rows whose indices or values differ from the
    reference's at any rank."""
    n = int(cfg["n"])
    ids = np.asarray(ids, np.int64)
    srt = np.sort(ids, axis=1)
    bad = int((((ids < 0) | (ids >= n)).any(axis=1)
               | (srt[:, 1:] == srt[:, :-1]).any(axis=1)).sum())
    ref_v, ref_i = ref
    differ = int(((ids != np.asarray(ref_i)).any(axis=1)
                  | (np.asarray(values, np.float64)
                     != np.asarray(ref_v, np.float64)).any(axis=1)).sum())
    return {"bad_ids": bad, "rows_differ": differ}
