"""Float32 L2 k-nearest-neighbour search over a float gallery.

What a configuration of this kind needs, in one place: the program
built through the public compile entry point, the data made from the
seed, the work a search needs, the plain reference, its
lower-precision control, and the comparison that decides ``correct``.
The reference and the control import nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST

#: the controls a chip reads, by name: keyword arguments of ``control``
CONTROLS = {"control_three_pass": {"dot": "three_pass"},
            "control_high": {"dot": "high"}}
#: the control a CPU reads: it computes float32 whatever the precision
#: asked for, so the bfloat16 passes are written out
CPU_CONTROL = "control_three_pass"


def build(cfg: dict):
    """The search program through ``compile_fn``: a traced
    ``(queries - gallery).norm().topk(k)`` kernel compiled onto the
    configured CAM subarrays, with every other option at its default."""
    from repro.core import ArchSpec, compile_fn

    k = int(cfg["k"])

    def knn_kernel(queries, gallery):
        diff = queries.unsqueeze(1).sub(gallery)     # (Q,1,D) - (N,D)
        return diff.norm(p=2, dim=-1).topk(k, largest=False)

    a = cfg["arch"]
    arch = ArchSpec(rows=a["rows"], cols=a["cols"], cam_type=a["cam_type"],
                    bits_per_cell=a["bits_per_cell"])
    example = [(cfg["query_rows_traced"], cfg["dim"]),
               (cfg["n"], cfg["dim"])]
    return compile_fn(knn_kernel, example, arch, cam_type=a["cam_type"],
                      value_bits=a["value_bits"])


def work(cfg: dict, rows: float) -> dict:
    """Operations and bytes one search of ``rows`` query rows needs,
    whatever implements it: the distance matrix is one
    ``rows x n x dim`` matmul, ``2 * rows * n * dim`` operations at the
    bf16 peak, and the float32 gallery is read once, ``n * dim * 4``
    bytes.  Query and result bytes are under a thousandth of the
    gallery and are left out."""
    if cfg["metric"] != "eucl":
        raise ValueError(f"no work count for metric {cfg['metric']!r}")
    n, dim = cfg["n"], cfg["dim"]
    return {"ops": 2.0 * rows * n * dim, "bytes": 4.0 * n * dim,
            "peak": "bf16_flops_per_s"}


def make_gallery(cfg: dict, key: jax.Array) -> jax.Array:
    """The gallery, drawn on the device in one call."""
    shape = (cfg["n"], cfg["dim"])
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


def make_queries(cfg: dict, rng: np.random.Generator, count: int,
                 rows: int) -> np.ndarray:
    """``count`` query blocks of ``rows`` rows, on the host."""
    return rng.standard_normal((count, rows, cfg["dim"]), dtype=np.float32)


def _dot_highest(q, g):
    return jnp.matmul(q, g.T, precision=_HIGHEST)


def _dot_three_pass(q, g):
    """``q @ g.T`` as three bfloat16 products accumulated in float32:
    each operand split into a bfloat16 head and a bfloat16 tail, the
    tail-by-tail product dropped.  This is the arithmetic of the TPU's
    ``Precision.HIGH``, written out so that it reads the same on every
    backend."""
    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    qh, ql = split(q)
    gh, gl = split(g)
    return (_dot_highest(qh, gh) + _dot_highest(qh, gl)
            + _dot_highest(ql, gh))


def _dot_high(q, g):
    return jnp.matmul(q, g.T, precision=jax.lax.Precision.HIGH)


_DOTS = {"highest": _dot_highest, "three_pass": _dot_three_pass,
         "high": _dot_high}


def _blocked_topk(queries: np.ndarray, gallery: jax.Array, k: int,
                  dot: str, block: int = 65536, qblock: int = 512):
    """Exact L2 top-k on the device, blocked over the gallery with a
    running top-k (ties to the lower index): ``(values, ids)`` on the
    host, values the squared distances ``|q|^2 + |p|^2 - 2 q.p``."""
    n, d = gallery.shape
    block = min(block, n)
    nb = -(-n // block)
    g = jnp.pad(gallery, ((0, nb * block - n), (0, 0))).reshape(nb, block, d)
    offs = jnp.arange(nb, dtype=jnp.int32) * block
    dotf = _DOTS[dot]

    @jax.jit       # the gallery is an argument: a closure would embed it
    def search(q, g, offs):
        qq = jnp.sum(q * q, axis=1, keepdims=True)

        def step(carry, xs):
            gb, off = xs
            dist = qq + jnp.sum(gb * gb, axis=1)[None, :] - 2.0 * dotf(q, gb)
            idx = off + jnp.arange(block, dtype=jnp.int32)
            dist = jnp.where(idx[None, :] < n, dist, jnp.inf)
            bv, bi = jax.lax.top_k(-dist, k)
            cv = jnp.concatenate([carry[0], bv], axis=1)
            ci = jnp.concatenate([carry[1], idx[bi]], axis=1)
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
    """The plain reference: float32 at ``precision=HIGHEST``."""
    return _blocked_topk(queries, gallery, int(cfg["k"]), "highest")


def control(cfg: dict, gallery: jax.Array, queries: np.ndarray,
            dot: str = "high"):
    """The reference one precision step down: float32 at ``high``, the
    backend's ``Precision.HIGH`` (three bfloat16 passes on a TPU).  A
    CPU computes float32 whatever the precision, so the CPU test asks
    for ``dot="three_pass"``, the passes written out."""
    return _blocked_topk(queries, gallery, int(cfg["k"]), dot)


def _distances_of(gallery: jax.Array, queries: np.ndarray,
                  ids: np.ndarray) -> np.ndarray:
    """The exact squared distance of each ``(query, id)`` pair, in
    float64 on the host."""
    rows = np.asarray(gallery[jnp.asarray(ids)], np.float64)   # (S, k, D)
    diff = rows - np.asarray(queries, np.float64)[:, None, :]
    return np.einsum("skd,skd->sk", diff, diff)


def compare(cfg: dict, gallery: jax.Array, queries: np.ndarray,
            values: np.ndarray, ids: np.ndarray, ref) -> dict:
    """The numbers that decide ``correct`` for served ``(values, ids)``
    of ``queries`` against ``ref = reference(...)``:

    * ``bad_ids``: rows with an index outside the gallery or repeated;
    * ``value_gap``: the widest gap between the served value and the
      reference's at the same rank, over the reference's value;
    * ``id_gap``: the widest gap between a served value and the exact
      (float64) distance of the served index, over that distance.
    """
    n = int(cfg["n"])
    ids = np.asarray(ids, np.int64)
    values = np.asarray(values, np.float64)
    in_range = (ids >= 0) & (ids < n)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    bad = int((~in_range.all(axis=1) | repeated).sum())
    ref_v = np.asarray(ref[0], np.float64)
    value_gap = float(np.max(np.abs(values - ref_v)
                             / np.maximum(np.abs(ref_v), 1e-30)))
    d = _distances_of(gallery, queries,
                      np.where(in_range, ids, 0).astype(np.int32))
    id_gap = float(np.max(np.abs(values - d) / np.maximum(np.abs(d), 1e-30)))
    return {"bad_ids": bad, "value_gap": value_gap, "id_gap": id_gap}
