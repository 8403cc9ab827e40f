"""Jitted public wrappers around the Pallas CAM-search kernels.

Semantics match `repro.kernels.ref` bit-for-bit (integer metrics) /
to float tolerance (analog).  ``cam_topk`` pads inputs to block multiples
on every call so the kernels only ever see aligned shapes; the search-plan
engine (`repro.core.engine`) instead hoists that padding behind its plan
cache — patterns are laid out once per stored array via
:func:`pad_to_blocks` and streamed straight into the kernels.
``interpret=None`` (the default everywhere) compiles through Mosaic on a
TPU backend and runs the kernel interpreter elsewhere
(``cam_search.resolve_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as kref
from .acam import acam_match_pallas, range_match_pallas
from .cam_search import (distance_pallas, fused_topk_pallas,
                         fused_topk_packed_pallas, lane_block, row_block)
from .hdc_encode import hdc_encode_pallas

__all__ = ["cam_topk", "cam_topk_packed", "pad_to_blocks", "cam_exact",
           "cam_range", "acam_match", "cam_range_match",
           "hdc_bind", "hdc_bundle", "hdc_permute", "hdc_encode"]


def pad_to_blocks(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    """Zero-pad a 2-D operand up to block multiples (rows, cols)."""
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("k", "largest", "tile_rows",
                                             "lanes_per_tile", "block_m",
                                             "interpret"))
def cam_topk_packed(qbits: jax.Array, pbits: jax.Array,
                    care: Optional[jax.Array] = None, *, k: int,
                    largest: bool = False, tile_rows: int = 128,
                    lanes_per_tile: int = 64, block_m: int = 128,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Fused best-match search over bit-packed binary/ternary operands.

    ``qbits`` (M, L) / ``pbits`` (N, L) are ``packing.pack_bits`` lanes;
    ``care`` (N, L) marks TCAM cared cells (wildcard = 0).  Results are
    bit-identical to ``cam_topk(metric="hamming")`` on the unpacked
    cells (counts are the same integers, candidate order is the same).
    """
    m, L = qbits.shape
    n = pbits.shape[0]
    k_eff = min(k, n)
    bn = lane_block(tile_rows, n)
    bl = lane_block(lanes_per_tile, L)
    bm = row_block(block_m, m)
    qp = pad_to_blocks(qbits, bm, bl)
    ppt = pad_to_blocks(pbits.T, bl, bn)
    cpt = None if care is None else pad_to_blocks(care.T, bl, bn)
    vals, idx = fused_topk_packed_pallas(
        qp, ppt, cpt, k=k_eff, largest=largest, n_valid=n, block_m=bm,
        block_n=bn, block_l=bl, interpret=interpret)
    return kref.pad_candidates(vals[:m], idx[:m], k, largest)


@functools.partial(jax.jit, static_argnames=("metric", "k", "largest",
                                             "tile_rows", "dims_per_tile",
                                             "block_m", "interpret"))
def cam_topk(queries: jax.Array, patterns: jax.Array, *, metric: str, k: int,
             largest: bool, tile_rows: int = 128, dims_per_tile: int = 512,
             block_m: int = 128, interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Fused CAM best-match search via the Pallas kernel.

    ``tile_rows``/``dims_per_tile`` take the role of the CAM subarray
    geometry (block_n / block_d); the cross-block candidate merge mirrors
    ``cim.merge_partial vertical``.
    """
    m, dim = queries.shape
    n = patterns.shape[0]
    k_eff = min(k, n)
    bn = lane_block(tile_rows, n)
    bd = lane_block(dims_per_tile, dim)
    bm = row_block(block_m, m)
    qp = pad_to_blocks(queries.astype(jnp.float32), bm, bd)
    pp = pad_to_blocks(patterns.astype(jnp.float32), bn, bd)
    vals, idx = fused_topk_pallas(qp, pp, metric=metric, k=k_eff,
                                  largest=largest, n_valid=n, block_m=bm,
                                  block_n=bn, block_d=bd,
                                  interpret=interpret)
    # k > N: pad with the shared losing sentinels (same helper the engine
    # and tiled reference use, so every path emits identical pad content)
    return kref.pad_candidates(vals[:m], idx[:m], k, largest)


# ---------------------------------------------------------------------------
# HDC hypervector encoding
# ---------------------------------------------------------------------------

#: bind / bundle / permute are pure jnp in every execution path (the
#: fused encode kernel inlines bind+bundle); the public wrappers jit the
#: pinned oracles so callers get one import surface for the HDC algebra
hdc_bind = jax.jit(kref.hdc_bind)
hdc_bundle = jax.jit(kref.hdc_bundle)
hdc_permute = jax.jit(kref.hdc_permute, static_argnames=("shift",))


@functools.partial(jax.jit, static_argnames=("block_m", "block_f", "block_h",
                                             "interpret"))
def hdc_encode(level_idx: jax.Array, keys: jax.Array, levels: jax.Array, *,
               block_m: int = 128, block_f: int = 256, block_h: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """(M, H) bipolar encodings via the fused Pallas kernel.

    Pads ``level_idx`` with level 0 and ``keys`` with zero rows (a
    padded feature's one-hot only ever hits zeroed key rows, so padding
    contributes nothing — see ``kernels/hdc_encode.py``), launches the
    kernel, and slices the valid block.  Bit-identical to
    :func:`ref.hdc_encode` (integer sums, sign tie -> +1).
    """
    m, f = level_idx.shape
    h = keys.shape[1]
    bm = row_block(block_m, m)
    bf = lane_block(block_f, f)
    bh = lane_block(block_h, h)
    qp = pad_to_blocks(level_idx.astype(jnp.int32), bm, bf)
    kp = pad_to_blocks(keys.astype(jnp.float32), bf, bh)
    lp = jnp.pad(levels.astype(jnp.float32), ((0, 0), (0, (-h) % bh)))
    out = hdc_encode_pallas(qp, kp, lp, block_m=bm, block_f=bf, block_h=bh,
                            interpret=interpret)
    return out[:m, :h]


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def cam_distances(queries: jax.Array, patterns: jax.Array, *, metric: str,
                  interpret: Optional[bool] = None) -> jax.Array:
    m, dim = queries.shape
    n = patterns.shape[0]
    bm, bn, bd = row_block(128, m), lane_block(128, n), lane_block(512, dim)
    qp = pad_to_blocks(queries.astype(jnp.float32), bm, bd)
    pp = pad_to_blocks(patterns.astype(jnp.float32), bn, bd)
    d = distance_pallas(qp, pp, metric=metric, block_m=bm, block_n=bn,
                        block_d=bd, interpret=interpret)
    return d[:m, :n]


def cam_exact(queries: jax.Array, patterns: jax.Array, *,
              metric: str = "hamming",
              interpret: Optional[bool] = None) -> jax.Array:
    return cam_distances(queries, patterns, metric=metric,
                         interpret=interpret) == 0


def cam_range(queries: jax.Array, patterns: jax.Array, threshold: float, *,
              metric: str = "hamming",
              interpret: Optional[bool] = None) -> jax.Array:
    return cam_distances(queries, patterns, metric=metric,
                         interpret=interpret) <= threshold


# ---------------------------------------------------------------------------
# aCAM range search (interval + fused threshold match)
# ---------------------------------------------------------------------------


@jax.jit
def acam_match(queries: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(M, N) boolean aCAM interval match via the fused Pallas kernel.

    Semantics pinned by :func:`ref.acam_match`: row ``j`` matches iff
    ``lo[j, d] <= q[i, d] <= hi[j, d]`` for all ``d`` (wildcard = full
    range).  Pure comparisons + integer counts, so kernel and oracle
    agree bit-for-bit.
    """
    m = queries.shape[0]
    n = lo.shape[0]
    out = acam_match_pallas(queries.astype(jnp.float32),
                            lo.astype(jnp.float32), hi.astype(jnp.float32),
                            n_valid=n)
    return out[:m, :n] != 0


@functools.partial(jax.jit, static_argnames=("metric", "threshold", "below",
                                             "interpret"))
def cam_range_match(queries: jax.Array, patterns: jax.Array, *, metric: str,
                    threshold: float, below: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """(M, N) boolean threshold match with the threshold fused in-kernel.

    Unlike :func:`cam_range` (distance matrix materialised as float32,
    compared on the host), the compare happens at block-extraction time
    and only an int8 matrix leaves the kernel — 4x less result traffic
    for the TH sensing mode.  Physical-metric contract matches
    :func:`ref.cam_range` on hamming/dot/eucl.
    """
    out = range_match_pallas(queries.astype(jnp.float32),
                             patterns.astype(jnp.float32), metric=metric,
                             threshold=threshold, below=below,
                             n_valid=patterns.shape[0], interpret=interpret)
    return out != 0
