"""Pallas TPU kernels for CAM search (fused distance + running top-k).

Hardware adaptation (DESIGN.md §2): a CAM subarray is a broadcast-compare-
reduce engine.  On TPU the profitable mapping is through the MXU: every
supported CAM metric decomposes into a matmul plus rank-1 row/column
corrections,

    hamming(q, p) = rowsum(q) + colsum(p) - 2 q.p      (q, p in {0,1})
    eucl^2(q, p)  = rowsum(q^2) + colsum(p^2) - 2 q.p
    dot(q, p)     =                              q.p

so one kernel covers all metrics with coefficients (alpha, beta, gamma).

For *binary/ternary* galleries there is additionally a packed-bit
XOR+popcount kernel (``fused_topk_packed_pallas``) over uint32 lanes
(``kernels.packing``): 32x less operand traffic, pure integer arithmetic,
bit-identical candidates.  On TPU it runs on the VPU rather than the MXU —
slower per *element* but the packed gallery moves 1/32nd the bytes, which
wins when the search is bandwidth-bound (de Lima et al., CAM-only DNN
inference).  The engine chooses per metric; analog metrics stay on the
float kernel.

Kernel structure (mirrors the CAM hierarchy):

* grid = (M/bm, N/bn, D/bd); the D axis accumulates the distance block in a
  VMEM scratch accumulator (like a subarray accumulating partial match-line
  counts across column tiles = ``cim.merge_partial horizontal``),
* at the last D step the kernel folds the block into the row block's
  running top-k (the subarray's winner-take-all periphery feeding the
  ``cim.merge_partial vertical`` tournament) — see ``_fold_block_topk``,
* the running top-k lives in the output block itself, which stays
  resident across the N and D axes: a lane-dense ``(bm, 128 * ceil(k /
  128))`` slab whose lanes past k hold the losing sentinels.  What leaves
  the kernel is the final top-k per query; nothing is merged after it.

Block shapes must be legal for the TPU tiling: the last block dimension
a multiple of 128 lanes (or the whole axis), the one before it a
multiple of 8 rows.  :func:`lane_block` / :func:`row_block` pick such
extents; callers pad operands to them (``ops.pad_to_blocks``).  VMEM
footprint = bm*bd + bn*bd + bm*bn + 2*bm*kp words (~0.6 MB at 128 x 256
x 256, k <= 128), well inside the default scoped budget with double
buffering.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packing import popcount32

__all__ = ["fused_topk_pallas", "fused_topk_packed_pallas",
           "distance_pallas", "METRIC_COEFFS", "lane_block", "row_block",
           "resolve_interpret"]

#: metric -> (alpha, beta, gamma, q_term, p_term)
METRIC_COEFFS = {
    "hamming": (-2.0, 1.0, 1.0, "x", "x"),
    "eucl": (-2.0, 1.0, 1.0, "x2", "x2"),
    "dot": (1.0, 0.0, 0.0, "none", "none"),
}

LANES = 128          # vreg lane width (last block dimension)
SUBLANES = 8         # vreg sublane count (second-to-last block dimension)
_NEG_BIG = -3.0e38
_POS_BIG = 3.0e38
_SENTINEL = 2 ** 30  # index of a losing slot (``ref.pad_candidates``)
_IDX_MAX = 2 ** 31 - 1


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compile through Mosaic on a TPU, interpret elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_block(want: int, total: int) -> int:
    """Block extent on a lane (last) axis of extent ``total``: ``want``
    rounded up to a multiple of 128 lanes, or the whole axis when that
    is no smaller."""
    b = _round_up(max(1, want), LANES)
    return total if b >= total else b


def row_block(want: int, total: int) -> int:
    """Block extent on a sublane (second-to-last) axis: ``want`` rounded
    up to a multiple of 8 rows, capped at ``total`` rounded up to 8."""
    return min(_round_up(max(1, want), SUBLANES),
               _round_up(max(1, total), SUBLANES))


def _term(x, kind):
    if kind == "x":
        return x
    if kind == "x2":
        return x * x
    return None


def _partial_distance(q, p, metric: str):
    """One D block of the MXU decomposition: ``(bm, bd) x (bn, bd) ->
    (bm, bn)`` float32, contracted at full float32 precision (the
    kernels' contract is the float32 oracle, not a bf16 pass)."""
    alpha, beta, gamma, qk, pk = METRIC_COEFFS[metric]
    q = q.astype(jnp.float32)
    p = p.astype(jnp.float32)
    part = alpha * jax.lax.dot_general(
        q, p, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    if beta:
        part = part + beta * jnp.sum(_term(q, qk), axis=1, keepdims=True)
    if gamma:
        part = part + gamma * jnp.sum(_term(p, pk), axis=1)[None, :]
    return part


def _packed_partial(q, pt, ct=None):
    """One lane block of the packed Hamming count: ``popcount(q ^ p [&
    care])`` summed over lanes, ``(bm, bl) x (bl, bn) -> (bm, bn)``.

    The pattern (and care) blocks arrive lane-major — ``(bl, bn)``, one
    packed lane per row, patterns along the 128-wide vreg lanes — so
    each lane is a 2-D ``(bm, 1) ^ (1, bn)`` broadcast and no
    ``(bm, bn, bl)`` temporary is ever formed.  Integer counts, exact
    in float32.
    """
    acc = jnp.zeros((q.shape[0], pt.shape[1]), jnp.int32)
    for lane in range(q.shape[1]):
        x = q[:, lane:lane + 1] ^ pt[lane:lane + 1, :]
        if ct is not None:
            x = x & ct[lane:lane + 1, :]
        acc = acc + popcount32(x)
    return acc.astype(jnp.float32)


def _fold_block_topk(dist, ov_ref, oi_ref, *, j, bn: int, k: int,
                     largest: bool, n_total: int):
    """Fold a ``(bm, bn)`` distance block into the row block's running
    top-k.

    ``ov_ref``/``oi_ref`` are the ``(bm, kp)`` output slab: lanes
    ``[0, k)`` hold the top-k so far, best first (value, then global
    index ascending); every other lane holds the losing sentinel
    (``ref.pad_candidates``: the losing infinity, index ``2**30``).
    Each of the k rounds takes the best remaining (key, index) pair over
    the running list and the block, and retires it from both — 2-D
    reductions and selects only.  Every block entry has a higher global
    index than every running entry, so this order over the union is
    exactly what a stable merge of per-block candidate lists gives,
    shared by the float and packed kernels alike.
    """
    bm = dist.shape[0]
    kp = ov_ref.shape[1]
    neg_inf = jnp.float32(-jnp.inf)

    @pl.when(j == 0)
    def _seed():
        ov_ref[...] = jnp.full((bm, kp), -jnp.inf if largest else jnp.inf,
                               jnp.float32)
        oi_ref[...] = jnp.full((bm, kp), _SENTINEL, jnp.int32)

    gidx = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1) + j * bn
    # padded pattern rows never beat a real one
    dist = jnp.where(gidx < n_total, dist,
                     _NEG_BIG if largest else _POS_BIG)
    bkey = dist if largest else -dist          # key domain: larger wins
    rv = ov_ref[...]
    rkey = rv if largest else -rv
    ridx = oi_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, kp), 1)

    def round_(t, c):
        rkey, ridx, bkey, gidx, okey, oidx = c
        best = jnp.maximum(jnp.max(rkey, axis=1, keepdims=True),
                           jnp.max(bkey, axis=1, keepdims=True))
        bi = jnp.minimum(
            jnp.min(jnp.where(rkey == best, ridx, _IDX_MAX), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(bkey == best, gidx, _IDX_MAX), axis=1,
                    keepdims=True))
        okey = jnp.where(lane == t, best, okey)
        oidx = jnp.where(lane == t, bi, oidx)
        taken = ridx == bi
        rkey = jnp.where(taken, neg_inf, rkey)
        ridx = jnp.where(taken, _SENTINEL, ridx)
        taken = gidx == bi
        bkey = jnp.where(taken, neg_inf, bkey)
        gidx = jnp.where(taken, _SENTINEL, gidx)
        return rkey, ridx, bkey, gidx, okey, oidx

    init = (rkey, ridx, bkey, gidx, jnp.full((bm, kp), neg_inf, jnp.float32),
            jnp.full((bm, kp), _SENTINEL, jnp.int32))
    *_, okey, oidx = jax.lax.fori_loop(0, k, round_, init)
    ov_ref[...] = okey if largest else -okey
    oi_ref[...] = oidx


def _topk_kernel(*refs, dist_fn: Callable, n_in: int, k: int, largest: bool,
                 n_total: int, bn: int, nd: int):
    """One (i, j, d) grid step; d accumulates, the last d folds the
    block into the running top-k."""
    ins, (ov_ref, oi_ref, acc_ref) = refs[:n_in], refs[n_in:]
    d = pl.program_id(2)
    j = pl.program_id(1)   # hoisted: program_id inside pl.when bodies does
    # not lower in interpret mode under jit

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dist_fn(*(r[...] for r in ins))

    @pl.when(d == nd - 1)
    def _fold():
        _fold_block_topk(acc_ref[...], ov_ref, oi_ref, j=j, bn=bn, k=k,
                         largest=largest, n_total=n_total)


def _topk_call(dist_fn: Callable, queries: jax.Array,
               patterns: Sequence[jax.Array], *, k: int, largest: bool,
               bm: int, bn: int, bd: int, n_valid: int | None,
               interpret: bool | None, lane_major: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Shared launch of the float and packed top-k kernels; patterns are
    ``(N, D)``, or ``(D, N)`` when ``lane_major``."""
    m, dim = queries.shape
    n = patterns[0].shape[1 if lane_major else 0]
    n_valid = n if n_valid is None else n_valid
    bm, bn, bd = min(bm, m), min(bn, n), min(bd, dim)
    nm, nn, nd = -(-m // bm), -(-n // bn), -(-dim // bd)
    k = min(k, n)
    kp = _round_up(k, LANES)

    kern = functools.partial(_topk_kernel, dist_fn=dist_fn,
                             n_in=1 + len(patterns), k=k, largest=largest,
                             n_total=n_valid, bn=bn, nd=nd)
    p_spec = (pl.BlockSpec((bd, bn), lambda i, j, d: (d, j)) if lane_major
              else pl.BlockSpec((bn, bd), lambda i, j, d: (j, d)))
    o_spec = pl.BlockSpec((bm, kp), lambda i, j, d: (i, 0))
    vals, idx = pl.pallas_call(
        kern,
        grid=(nm, nn, nd),
        in_specs=[pl.BlockSpec((bm, bd), lambda i, j, d: (i, d))]
        + [p_spec] * len(patterns),
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((nm * bm, kp), jnp.float32),
                   jax.ShapeDtypeStruct((nm * bm, kp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(queries, *patterns)
    return vals[:m, :k], idx[:m, :k]


def fused_topk_pallas(queries: jax.Array, patterns: jax.Array, *, metric: str,
                      k: int, largest: bool, block_m: int = 128,
                      block_n: int = 128, block_d: int = 512,
                      n_valid: int | None = None,
                      interpret: bool | None = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Fused distance + top-k: returns ``(M, min(k, N))`` values/indices,
    best first, ties to the lower index.

    Operands must be padded to multiples of the block shape, which must
    be tiling-legal (:func:`lane_block` / :func:`row_block`).
    ``n_valid``: number of real pattern rows (rows >= n_valid are
    padding and never win).
    """
    return _topk_call(functools.partial(_partial_distance, metric=metric),
                      queries, [patterns], k=k, largest=largest, bm=block_m,
                      bn=block_n, bd=block_d, n_valid=n_valid,
                      interpret=interpret)


def fused_topk_packed_pallas(qbits: jax.Array, pbits_t: jax.Array,
                             care_t: jax.Array | None = None, *, k: int,
                             largest: bool, block_m: int = 128,
                             block_n: int = 128, block_l: int = 128,
                             n_valid: int | None = None,
                             interpret: bool | None = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """Packed binary/ternary variant of :func:`fused_topk_pallas`.

    Operands are uint32 lane arrays (``packing.pack_bits``): ``qbits``
    (M, L), and lane-major ``pbits_t`` (L, N) with an optional
    per-pattern TCAM care mask ``care_t`` (L, N) — the transposed
    layout keeps a narrow code dense in HBM, where an (N, L) uint32
    array pads L up to 128 lanes.  The distance block is
    ``popcount(q ^ p [& care])``
    accumulated over lane blocks — integer arithmetic end to end, so
    results are bit-identical to the unpacked hamming path (same
    fold, same candidate ordering) at 1/32nd the operand traffic.
    On TPU this path runs on the VPU (bitwise + popcount); it exists
    for bandwidth-bound packed galleries, whereas the float kernel
    feeds the MXU — the engine picks per metric/dtype.
    """
    pats = [pbits_t] if care_t is None else [pbits_t, care_t]
    return _topk_call(_packed_partial, qbits, pats, k=k, largest=largest,
                      bm=block_m, bn=block_n, bd=block_l, n_valid=n_valid,
                      interpret=interpret, lane_major=True)


def _dist_kernel(q_ref, p_ref, o_ref, *, metric: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _partial_distance(q_ref[...], p_ref[...], metric)


def distance_pallas(queries: jax.Array, patterns: jax.Array, *, metric: str,
                    block_m: int = 128, block_n: int = 128,
                    block_d: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """Full (M, N) distance matrix (used by exact/range match)."""
    m, dim = queries.shape
    n = patterns.shape[0]
    bm = min(block_m, max(8, m))
    bn = min(block_n, max(8, n))
    bd = min(block_d, dim)
    nm, nn, nd = -(-m // bm), -(-n // bn), -(-dim // bd)
    kern = functools.partial(_dist_kernel, metric=metric)
    out = pl.pallas_call(
        kern,
        grid=(nm, nn, nd),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, d: (i, d)),
            pl.BlockSpec((bn, bd), lambda i, j, d: (j, d)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, d: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(queries, patterns)
    return out[:m, :n]
