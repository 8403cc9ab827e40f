"""Pallas TPU kernels for analog-CAM range search (interval + threshold).

An analog CAM cell stores an *interval* ``[lo, hi]`` and matches while
the analog input voltage lies inside it (Li et al., *Analog content
addressable memories with memristors*); a row's match line stays high
iff every cell matches.  That single primitive executes a root-to-leaf
decision-tree branch in one search (Pedretti et al., *Tree-based
machine learning performed in-memory with memristive analog CAM*) —
the flagship non-KNN CAM workload.

Two fused kernels, both emitting a compact ``int8`` match matrix
instead of a float distance surface:

* ``acam_match_pallas`` — interval match: grid ``(M/bm, N/bn, D/bd)``,
  the D axis accumulates per-block *violation counts*
  (``q < lo or q > hi`` per cell) in a VMEM scratch, and the last D
  step writes ``violations == 0``.  A wildcard dimension is a
  full-range interval (``lo = -inf``/``hi = +inf``) and can never add
  a violation.  Counts are integers in float32 (exact), so the result
  equals ``ref.acam_match`` bit-for-bit under any tiling.
* ``range_match_pallas`` — thresholded variant of the existing
  distance kernels: the same MXU matmul decomposition as
  ``cam_search._fused_kernel`` accumulates the distance block, the
  last D step converts to the logical metric domain (``dot = D - 2h``
  for bipolar search) and writes ``dist <= tau`` (or ``>= tau``) —
  the paper's TH sensing mode, batched over queries.

Padding contract (shared with the engine layouts): zero-padded
dimensions carry ``q = lo = hi = 0`` / ``q = p = 0`` and contribute no
violation / no mismatch; pattern rows at or beyond ``n_total`` are
forced to non-match and sliced off by the wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cam_search import _partial_distance, resolve_interpret

__all__ = ["acam_match_pallas", "range_match_pallas"]


def _pad_f32(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad to block multiples as float32 (the kernels' shared
    padding contract: zero-padded dims can never add a violation or a
    mismatch).  Mirrors ``ops.pad_to_blocks``, which cannot be imported
    here (``ops`` imports this module)."""
    pr, pc = (-x.shape[0]) % rows, (-x.shape[1]) % cols
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x.astype(jnp.float32)


def _write_match(acc, o_ref, *, j: int, bn: int, n_total: int, tau: float,
                 below: bool, to_logical: str, dim: int):
    """Threshold + row-mask + int8 store shared by both kernels.

    ``to_logical``: ``"identity"`` keeps the accumulated value,
    ``"bipolar"`` converts a physical Hamming count to the dot/cos
    domain (``v = dim - 2h``) — the same elementwise translation the
    jnp engine path applies, so the compare sees identical floats.
    """
    v = acc if to_logical == "identity" else float(dim) - 2.0 * acc
    hit = (v <= tau) if below else (v >= tau)
    col = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    hit = hit & (col + j * bn < n_total)     # padded rows never match
    o_ref[...] = hit.astype(jnp.int8)


def _interval_kernel(q_ref, lo_ref, hi_ref, o_ref, acc_ref, *, nd: int,
                     n_total: int, bn: int):
    """One (i, j, d) grid step of the interval match: d accumulates the
    violation count, the last d emits ``violations == 0``."""
    d = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)[:, None, :]
    lo = lo_ref[...].astype(jnp.float32)[None, :, :]
    hi = hi_ref[...].astype(jnp.float32)[None, :, :]
    viol = ((q < lo) | (q > hi)).sum(-1)
    acc_ref[...] += viol.astype(jnp.float32)

    @pl.when(d == nd - 1)
    def _emit():
        _write_match(acc_ref[...], o_ref, j=j, bn=bn, n_total=n_total,
                     tau=0.0, below=True, to_logical="identity", dim=0)


def acam_match_pallas(queries: jax.Array, lo: jax.Array, hi: jax.Array, *,
                      block_m: int = 128, block_n: int = 128,
                      block_d: int = 128, n_valid: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """(M, N) int8 interval-match matrix (1 = row matches the query).

    ``queries`` (M, D); ``lo``/``hi`` (N, D) per-row interval bounds.
    Inputs need not be block-aligned — zero padding is applied here
    (zero-width padded intervals match the zero-padded query dims, so
    padding never flips a result; ``n_valid`` masks padded rows).
    """
    m, dim = queries.shape
    n = lo.shape[0]
    n_valid = n if n_valid is None else n_valid
    bm = min(block_m, max(8, m))
    bn = min(block_n, max(8, n))
    bd = min(block_d, dim)
    nm, nn, nd = -(-m // bm), -(-n // bn), -(-dim // bd)

    qp = _pad_f32(queries, bm, bd)
    lop, hip = _pad_f32(lo, bn, bd), _pad_f32(hi, bn, bd)
    kern = functools.partial(_interval_kernel, nd=nd, n_total=n_valid, bn=bn)
    out = pl.pallas_call(
        kern,
        grid=(nm, nn, nd),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, d: (i, d)),
            pl.BlockSpec((bn, bd), lambda i, j, d: (j, d)),
            pl.BlockSpec((bn, bd), lambda i, j, d: (j, d)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, d: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qp, lop, hip)
    return out[:m, :n]


def _range_kernel(q_ref, p_ref, o_ref, acc_ref, *, metric: str, nd: int,
                  n_total: int, bn: int, tau: float, below: bool,
                  to_logical: str, dim: int):
    """Distance accumulation (MXU decomposition) + threshold at last d."""
    d = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _partial_distance(q_ref[...], p_ref[...], metric)

    @pl.when(d == nd - 1)
    def _emit():
        _write_match(acc_ref[...], o_ref, j=j, bn=bn, n_total=n_total,
                     tau=tau, below=below, to_logical=to_logical, dim=dim)


def range_match_pallas(queries: jax.Array, patterns: jax.Array, *,
                       metric: str, threshold: float, below: bool = True,
                       to_logical: str = "identity", dim: int | None = None,
                       block_m: int = 128, block_n: int = 128,
                       block_d: int = 512, n_valid: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """(M, N) int8 threshold-match matrix (TH sensing, ``dist <= tau``).

    ``metric`` is the *physical* metric (hamming / dot / eucl — the
    MXU decomposition); ``to_logical="bipolar"`` converts the Hamming
    count to ``dim - 2h`` before the compare, mirroring the engine's
    metric-domain translation bit-for-bit.
    """
    m, d_ = queries.shape
    n = patterns.shape[0]
    n_valid = n if n_valid is None else n_valid
    dim = d_ if dim is None else dim
    bm = min(block_m, max(8, m))
    bn = min(block_n, max(8, n))
    bd = min(block_d, d_)
    nm, nn, nd = -(-m // bm), -(-n // bn), -(-d_ // bd)

    qp, pp = _pad_f32(queries, bm, bd), _pad_f32(patterns, bn, bd)
    kern = functools.partial(_range_kernel, metric=metric, nd=nd,
                             n_total=n_valid, bn=bn, tau=float(threshold),
                             below=below, to_logical=to_logical, dim=dim)
    out = pl.pallas_call(
        kern,
        grid=(nm, nn, nd),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, d: (i, d)),
            pl.BlockSpec((bn, bd), lambda i, j, d: (j, d)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, d: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qp, pp)
    return out[:m, :n]
