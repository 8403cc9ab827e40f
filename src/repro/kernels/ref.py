"""Pure-jnp oracles for the CAM search kernels.

These define the *semantics* that both the Pallas TPU kernels
(`repro.kernels.cam_search`) and the C4CAM functional executor must match
bit-for-bit (integer metrics) or to float tolerance (analog metrics).

Conventions
-----------
queries  : (M, D)  — one query per row
patterns : (N, D)  — the stored CAM content ("database")
returns  : (values, indices), each (M, K)

Metrics
-------
* ``hamming``  — # of mismatching cells; inputs are {0,1} (or booleans).
* ``dot``      — inner product; for bipolar +-1 data ``dot = D - 2*hamming``.
* ``eucl``     — squared L2 distance (sqrt is monotone; CAM sensing
  compares squared sums, so we keep squares end-to-end).
* ``cos``      — cosine similarity.

Match types
-----------
* best-k  : top-k by value (largest=True for similarities, False for
  distances) with deterministic lowest-index tie-breaking.
* exact   : rows with distance == 0 (boolean match vector).
* range   : rows with distance <= threshold (boolean match vector).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["distances", "packed_distances", "ternary_distances",
           "tile_distance", "tiled_distances", "cam_topk",
           "cam_topk_ternary", "cam_exact", "cam_range", "acam_match",
           "acam_violations", "cam_topk_tiled", "merge_topk",
           "pad_candidates", "hdc_bind", "hdc_bundle", "hdc_permute",
           "hdc_encode"]


#: float32 contractions at full precision: a TPU's default rounds float32
#: operands to bfloat16, which no float32 distance contract allows
_HIGHEST = jax.lax.Precision.HIGHEST


def distances(queries: jax.Array, patterns: jax.Array, metric: str) -> jax.Array:
    """(M, N) distance/similarity matrix."""
    q = queries.astype(jnp.float32)
    p = patterns.astype(jnp.float32)
    if metric == "hamming":
        # mismatch count; inputs {0,1}
        return (q[:, None, :] != p[None, :, :]).sum(-1).astype(jnp.float32)
    if metric == "dot":
        return jnp.matmul(q, p.T, precision=_HIGHEST)
    if metric == "eucl":
        # squared L2 via expansion (matches tiled partial-sum accumulation)
        qq = (q * q).sum(-1, keepdims=True)
        pp = (p * p).sum(-1)
        return qq + pp[None, :] - 2.0 * jnp.matmul(q, p.T, precision=_HIGHEST)
    if metric == "cos":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        pn = p / jnp.maximum(jnp.linalg.norm(p, axis=-1, keepdims=True), 1e-12)
        return jnp.matmul(qn, pn.T, precision=_HIGHEST)
    raise ValueError(f"unknown metric {metric!r}")


def packed_distances(qbits: jax.Array, pbits: jax.Array,
                     care: jax.Array | None = None) -> jax.Array:
    """(M, N) Hamming distances on bit-packed uint32 operands.

    ``qbits``: (M, L), ``pbits``: (N, L) — :func:`packing.pack_bits`
    lanes.  ``hamming = popcount(q ^ p)``; with a packed per-pattern
    ``care`` mask (N, L) the TCAM wildcard search is
    ``popcount((q ^ p) & care)`` — cells whose care bit is clear can
    never mismatch.  Bit-identical (as integers) to
    :func:`distances(metric="hamming")` / :func:`ternary_distances` on
    the unpacked cells, because both count exactly the same mismatching
    positions.  Returned as float32 to match the unpacked kernels
    (counts are < 2**24, so the conversion is exact).
    """
    from .packing import popcount32

    x = qbits[:, None, :] ^ pbits[None, :, :]
    if care is not None:
        x = x & care[None, :, :]
    return popcount32(x).sum(-1).astype(jnp.float32)


def ternary_distances(queries: jax.Array, patterns: jax.Array,
                      care: jax.Array) -> jax.Array:
    """(M, N) TCAM wildcard Hamming distance on *unpacked* cells.

    ``care``: (N, D) per-pattern mask — non-zero entries are compared,
    zero entries are "don't care" wildcards that never mismatch.  This
    is the semantic oracle the packed ternary kernels must match
    bit-for-bit (integer counts).
    """
    mism = queries[:, None, :] != patterns[None, :, :]
    return (mism & (care[None, :, :] != 0)).sum(-1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# HDC hypervector algebra (bipolar {-1, +1} convention)
# ---------------------------------------------------------------------------


def hdc_bind(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise bind of bipolar hypervectors: multiplication.

    For bipolar ±1 data, multiply *is* XOR in the sign domain
    (``(-1)^(x ^ y)``), which is the TCAM-friendly binding the packed
    engine path exploits — binding never changes the alphabet.
    """
    return (a * b).astype(jnp.float32)


def hdc_bundle(stack: jax.Array) -> jax.Array:
    """Majority bundle along axis 0: sign of the elementwise sum.

    Ties (an even stack splitting evenly) resolve to **+1** — the
    deterministic contract every execution path (oracle, fused encode
    kernel, classifier AM refresh) must share for bit-identity.
    """
    s = jnp.sum(stack.astype(jnp.float32), axis=0)
    return jnp.where(s >= 0, 1.0, -1.0).astype(jnp.float32)


def hdc_permute(x: jax.Array, shift: int) -> jax.Array:
    """Cyclic permutation (roll) along the hypervector dimension —
    the sequence/position operator of the HDC algebra."""
    return jnp.roll(x, shift, axis=-1)


def hdc_encode(level_idx: jax.Array, keys: jax.Array,
               levels: jax.Array) -> jax.Array:
    """Record-based hypervector encoding — the semantic oracle.

    ``level_idx``: (M, F) int quantised feature levels; ``keys``: (F, H)
    bipolar per-feature (position) hypervectors; ``levels``: (L, H)
    bipolar level hypervectors.  Sample ``m`` encodes as the majority
    bundle over features of ``bind(keys[f], levels[level_idx[m, f]])``,
    tie -> +1 (:func:`hdc_bundle`).  All sums are small integers, exact
    in float32 — the fused Pallas kernel's matmul decomposition
    (:mod:`repro.kernels.hdc_encode`) reproduces them bit-for-bit.

    Materialises the dense (M, F, H) bound tensor: oracle use only (the
    production paths are the fused kernel and the one-hot matmul
    decomposition in :mod:`repro.hdc.encoding`).
    """
    bound = keys[None, :, :].astype(jnp.float32) * \
        levels.astype(jnp.float32)[level_idx]              # (M, F, H)
    s = bound.sum(axis=1)
    return jnp.where(s >= 0, 1.0, -1.0).astype(jnp.float32)


def _topk_with_ties(scores: jax.Array, k: int, largest: bool
                    ) -> Tuple[jax.Array, jax.Array]:
    """Deterministic top-k: ties broken toward the lower index.

    ``jax.lax.top_k`` is stable (equal elements keep ascending-index order),
    which we rely on for bit-exact equivalence between the dense and tiled
    execution paths.
    """
    key = scores if largest else -scores
    _, idx = jax.lax.top_k(key, k)
    true_vals = jnp.take_along_axis(scores, idx, axis=-1)
    return true_vals, idx.astype(jnp.int32)


@partial(jax.jit, static_argnames=("metric", "k", "largest"))
def cam_topk(queries: jax.Array, patterns: jax.Array, *, metric: str,
             k: int, largest: bool) -> Tuple[jax.Array, jax.Array]:
    """Best-match search: top-k rows of ``patterns`` per query."""
    d = distances(queries, patterns, metric)
    return _topk_with_ties(d, k, largest)


@partial(jax.jit, static_argnames=("k", "largest"))
def cam_topk_ternary(queries: jax.Array, patterns: jax.Array,
                     care: jax.Array, *, k: int, largest: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """TCAM wildcard best-match: top-k by care-masked Hamming distance."""
    d = ternary_distances(queries, patterns, care)
    return _topk_with_ties(d, k, largest)


@partial(jax.jit, static_argnames=("metric",))
def cam_exact(queries: jax.Array, patterns: jax.Array, *, metric: str = "hamming"
              ) -> jax.Array:
    """(M, N) boolean exact-match matrix (distance == 0)."""
    d = distances(queries, patterns, metric)
    return d == 0


@partial(jax.jit, static_argnames=("metric",))
def cam_range(queries: jax.Array, patterns: jax.Array, threshold: float,
              *, metric: str = "hamming") -> jax.Array:
    """(M, N) boolean threshold-match matrix (distance <= threshold).

    The paper's TH sensing mode: a row matches iff its distance is at
    or below the threshold — ties are *inclusive* (a match-line that
    discharges exactly at the reference level still latches).  For
    similarity metrics (``dot``/``cos``) the same ``<=`` contract holds
    on the similarity value; callers wanting "at least this similar"
    negate or use the engine's ``below=False`` range programs.
    """
    d = distances(queries, patterns, metric)
    return d <= threshold


def acam_violations(queries: jax.Array, lo: jax.Array, hi: jax.Array
                    ) -> jax.Array:
    """(M, N) count of interval violations per (query, row) pair.

    ``lo``/``hi``: (N, D) per-row per-dimension interval bounds of an
    analog CAM (each aCAM cell stores an interval and matches iff the
    analog input falls inside it — Li et al., *Analog content
    addressable memories with memristors*).  A wildcard dimension is a
    full-range interval (``lo = -inf, hi = +inf``), which can never be
    violated.  Counts are small integers returned as float32 (exact),
    and they are *additive over dimension tiles* — the tiled engine
    path accumulates per-column-tile partial counts and reproduces the
    dense count bit-for-bit.
    """
    q = queries.astype(jnp.float32)[:, None, :]
    viol = (q < lo.astype(jnp.float32)[None, :, :]) | \
        (q > hi.astype(jnp.float32)[None, :, :])
    return viol.sum(-1).astype(jnp.float32)


@jax.jit
def acam_match(queries: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(M, N) boolean aCAM interval-match matrix.

    Row ``j`` matches query ``i`` iff ``lo[j, d] <= q[i, d] <= hi[j, d]``
    for every dimension ``d`` — the analog CAM match-line stays charged
    only when all cells are inside their stored interval.  This is the
    semantic contract the Pallas interval kernel and the engine's
    ``RangePlan`` interval mode must match exactly (pure comparisons and
    integer counts: no arithmetic, so the result is tiling-invariant).
    """
    return acam_violations(queries, lo, hi) == 0


def tile_distance(q_t: jax.Array, p_t: jax.Array, metric: str) -> jax.Array:
    """One column tile's (M, rows) partial distance block.

    The *single* definition of the per-tile arithmetic every tiled path
    shares — :func:`cam_topk_tiled`, :func:`tiled_distances`, and the
    engine's scan executables all accumulate exactly these float
    operations, which is what makes their bit-identity a structural
    property rather than a maintained coincidence.
    """
    if metric == "hamming":
        return (q_t[:, None, :] != p_t[None, :, :]).sum(-1).astype(jnp.float32)
    if metric == "dot":
        return jnp.matmul(q_t, p_t.T, precision=_HIGHEST)
    if metric == "eucl":
        qq = (q_t * q_t).sum(-1, keepdims=True)
        ppv = (p_t * p_t).sum(-1)
        return qq + ppv[None, :] - 2.0 * jnp.matmul(q_t, p_t.T,
                                                    precision=_HIGHEST)
    raise ValueError(f"tiled path does not support metric {metric!r}")


def tiled_distances(queries: jax.Array, patterns: jax.Array, *, metric: str,
                    tile_rows: int, dims_per_tile: int) -> jax.Array:
    """(M, N) distance matrix with *tiled* partial-sum accumulation.

    Same per-column-tile arithmetic (:func:`tile_distance`) and
    left-to-right accumulation order as :func:`cam_topk_tiled` — this
    is the distance surface the partitioned hardware actually senses,
    and the oracle the engine's ``RangePlan`` threshold path must match
    bit-for-bit (identical float operations in identical order, for
    *every* metric including eucl).  Bit-identical to
    :func:`distances` for the integer metrics.
    """
    m, dim = queries.shape
    n = patterns.shape[0]
    gr = -(-n // tile_rows)
    gc = -(-dim // dims_per_tile)
    qp = jnp.pad(queries.astype(jnp.float32),
                 ((0, 0), (0, gc * dims_per_tile - dim)))
    pp = jnp.pad(patterns.astype(jnp.float32),
                 ((0, gr * tile_rows - n), (0, gc * dims_per_tile - dim)))

    rows = []
    for r in range(gr):
        p_rows = pp[r * tile_rows:(r + 1) * tile_rows]
        dist = None
        for c in range(gc):
            sl = slice(c * dims_per_tile, (c + 1) * dims_per_tile)
            part = tile_distance(qp[:, sl], p_rows[:, sl], metric)
            dist = part if dist is None else dist + part   # horizontal merge
        rows.append(dist)
    return jnp.concatenate(rows, axis=-1)[:, :n]


def pad_candidates(vals: jax.Array, idx: jax.Array, k: int, largest: bool
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pad an (M, k') candidate list up to k with losing sentinels.

    Shared by the tiled reference below and the search-plan engine
    (`repro.core.engine`) so both paths emit identical pad content —
    the stable merges rely on that for bit-exact equivalence.
    """
    short = k - vals.shape[-1]
    if short <= 0:
        return vals, idx
    lose = -jnp.inf if largest else jnp.inf
    return (jnp.pad(vals, ((0, 0), (0, short)), constant_values=lose),
            jnp.pad(idx, ((0, 0), (0, short)), constant_values=2 ** 30))


def merge_topk(values_a: jax.Array, idx_a: jax.Array, values_b: jax.Array,
               idx_b: jax.Array, *, k: int, largest: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """Vertical merge of two (M, k) candidate lists (cam.merge_partial)."""
    vals = jnp.concatenate([values_a, values_b], axis=-1)
    idxs = jnp.concatenate([idx_a, idx_b], axis=-1)
    key = vals if largest else -vals
    # stability of lax.top_k + "lists concatenated in ascending global row
    # order" gives lower-global-index tie-breaking, matching cam_topk.
    _, sel = jax.lax.top_k(key, k)
    return (jnp.take_along_axis(vals, sel, axis=-1),
            jnp.take_along_axis(idxs, sel, axis=-1))


def cam_topk_tiled(queries: jax.Array, patterns: jax.Array, *, metric: str,
                   k: int, largest: bool, tile_rows: int, dims_per_tile: int,
                   care: jax.Array | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Reference for the *tiled* (partitioned) execution path.

    Mirrors the compulsory-partitioning semantics exactly: horizontal
    accumulation of per-column-tile partial distances, per-row-tile top-k,
    then vertical tournament merge with global index offsets.  Must equal
    :func:`cam_topk` for additive metrics (hamming / dot / eucl).

    ``care`` (hamming only): per-pattern (N, D) TCAM wildcard mask —
    zero entries never mismatch (see :func:`ternary_distances`).  The
    mask is additive over column tiles like the plain mismatch count, so
    the tiled result equals the dense oracle bit-for-bit.
    """
    m, dim = queries.shape
    n = patterns.shape[0]
    gr = -(-n // tile_rows)
    gc = -(-dim // dims_per_tile)
    pad_n = gr * tile_rows - n
    pad_d = gc * dims_per_tile - dim
    fill = 0.0
    qp = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, pad_d)))
    pp = jnp.pad(patterns.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    if care is not None:
        if metric != "hamming":
            raise ValueError("care masks require metric='hamming'")
        cp = jnp.pad((jnp.asarray(care) != 0).astype(jnp.float32),
                     ((0, pad_n), (0, pad_d)))

    def col_tile(ct, q_t, p_t, c_t=None):
        if c_t is not None:
            return ((q_t[:, None, :] != p_t[None, :, :])
                    & (c_t[None, :, :] != 0)).sum(-1).astype(jnp.float32)
        return tile_distance(q_t, p_t, metric)

    acc_v = acc_i = None
    for r in range(gr):
        p_rows = pp[r * tile_rows:(r + 1) * tile_rows]
        c_rows = cp[r * tile_rows:(r + 1) * tile_rows] if care is not None \
            else None
        dist = None
        for c in range(gc):
            sl = slice(c * dims_per_tile, (c + 1) * dims_per_tile)
            part = col_tile(c, qp[:, sl], p_rows[:, sl],
                            None if c_rows is None else c_rows[:, sl])
            dist = part if dist is None else dist + part   # horizontal merge
        # mask padded rows so they never win
        if r == gr - 1 and pad_n:
            bad = jnp.full((m, pad_n), -jnp.inf if largest else jnp.inf)
            dist = dist.at[:, tile_rows - pad_n:].set(bad)
        v, i = _topk_with_ties(dist, min(k, tile_rows), largest)
        v, i = pad_candidates(v, i + r * tile_rows, k, largest)
        if acc_v is None:
            acc_v, acc_i = v, i
        else:
            acc_v, acc_i = merge_topk(acc_v, acc_i, v, i, k=k, largest=largest)
    return acc_v, acc_i
