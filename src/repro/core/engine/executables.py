"""Backend executables: the jitted prepare / chunk / row-update triples.

Every builder returns ``(prepare, chunk_fn, row_update)``:

* ``prepare(*stored)`` encodes / packs / lays out the stored operands
  as per-subarray tile leaves (hoisted behind the plan's pattern memo);
* ``chunk_fn(q_chunk, prepared)`` executes one query micro-batch —
  top-k candidates for similarity plans, a boolean match block for
  range plans;
* ``row_update(prepared, new_srcs, idx, donate)`` re-lays only the row
  tiles touched by a gallery mutation (see ``PlanBase.update_rows``).

Three backends per family: the jnp reference-tiled scan, the sharded
``shard_map`` variant (collective-free per-device programs + host-side
:func:`merge_shard_candidates`), and the fused Pallas kernels.  The
*tiny* builders collapse a small single-column-tile grid into one dense
tile — same arithmetic, no ``lax.scan`` — for the small-program fast
path (see ``docs/engine.md``).

Numerical contract: each executable performs the *same* arithmetic in
the same order as the interpreted tile ops — bit-identical results for
the integer metrics (hamming / dot / packed popcounts / interval
violation counts), float-tolerance for eucl / cos — as pinned by
``repro.kernels.ref``.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...kernels import packing as kpack
from ...kernels import ref as kref
from ...launch.mesh import make_data_mesh
from .spec import (RangeSpec, SimilaritySpec, _bits, _encode, _metric_values)


def _tile_rows_block(arr: jax.Array, tiles: jax.Array, tr: int,
                     n: int) -> jax.Array:
    """Gather whole row tiles out of a stored operand (jit-traceable).

    Returns the ``(len(tiles) * tr, dim)`` row block covering the given
    row tiles, with slots at/beyond row ``n`` zeroed — exactly the
    content a full prepare lays out for those tiles (it zero-pads
    ragged rows *after* encoding, but every cell encoding maps 0 -> 0,
    so zeroing the raw rows first is equivalent).
    """
    tiles = jnp.asarray(tiles, jnp.int32)
    row_ids = (tiles[:, None] * tr
               + jnp.arange(tr, dtype=jnp.int32)).reshape(-1)
    valid = row_ids < n
    block = jnp.asarray(arr)[jnp.minimum(row_ids, n - 1)]
    return jnp.where(valid[:, None], block, 0)


def _col_dist_fn(spec: SimilaritySpec, packed: bool) -> Callable:
    """Per-column-tile partial distance: ``f(qc, pr) -> (B, tr) float32``.

    ``pr`` is the tuple of per-tile pattern leaves — ``(patterns,)`` or
    ``(patterns, care)`` for ternary.  Unpacked leaves are float slabs
    fed to the oracle arithmetic; packed leaves are uint32 lanes fed to
    XOR+popcount.  Both produce the *same integers* for the integer
    metrics (exact in float32), so the tournament downstream is
    bit-identical whichever representation runs.
    """
    phys_metric, _, _ = _metric_values(spec.metric, spec.largest)
    ternary = spec.care_arg is not None
    if packed:
        def f(qc, pr):
            return kref.packed_distances(qc, pr[0],
                                         pr[1] if ternary else None)
        return f
    if ternary:
        return lambda qc, pr: kref.ternary_distances(qc, pr[0], pr[1])
    return lambda qc, pr: kref.distances(qc, pr[0], phys_metric)


def _tile_tournament(spec: SimilaritySpec, col_dist: Callable,
                     unroll: int = 1):
    """The row-tile tournament shared by the single-device and sharded
    executables: ``scan(qt, pt, roffs)`` runs the column-tile partial-sum
    scan + per-tile top-k + vertical ``merge_topk`` tournament over the
    row tiles in ``pt`` (physical domain), with global row offsets
    ``roffs``.  ``pt`` is a tuple of pattern leaves (see
    :func:`_col_dist_fn`), each ``(gr, gc, tr, lanes-or-dpt)``.  One
    definition keeps every execution path bit-identical by construction.

    Shape-polymorphic in the query batch (read off ``qt``): the
    standard chunked path always traces at the plan's micro-batch, the
    tiny fast path traces at the caller's query count.
    """
    k = spec.k
    _, _, phys_largest = _metric_values(spec.metric, spec.largest)
    tr = spec.tile_rows
    n = spec.n
    kk = min(k, tr)
    lose = -jnp.inf if phys_largest else jnp.inf
    # rows beyond the unsharded physical extent exist only on shard-
    # padding tiles; their candidates become pad_candidates sentinels
    # (a no-op for the single-device grid, which never exceeds it)
    n_phys = spec.grid_rows * tr
    # unroll is a tuning knob, never a semantic one: lax.scan executes
    # identical steps in identical order at any factor.  Clamp to each
    # scan's static length (the sharded executable scans tiles-per-
    # shard, not grid_rows, so the clamp reads the traced operands).
    unroll = max(1, int(unroll))

    def tile_topk(qt, pr, roff):
        """Per-row-tile candidate list (pr leaves: (gc, tr, ...))."""
        batch = qt.shape[1]

        def col_step(acc, xs):
            qc = xs[0]                  # horizontal merge, oracle arithmetic
            return acc + col_dist(qc, xs[1:]), None

        dist, _ = jax.lax.scan(
            col_step, jnp.zeros((batch, tr), jnp.float32), (qt, *pr),
            unroll=min(unroll, qt.shape[0]))
        gidx = roff + jnp.arange(tr, dtype=jnp.int32)
        dist = jnp.where(gidx[None, :] < n, dist, lose)      # ragged rows
        key = dist if phys_largest else -dist
        _, idx = jax.lax.top_k(key, kk)
        v = jnp.take_along_axis(dist, idx, axis=-1)
        i = idx.astype(jnp.int32) + roff
        i = jnp.where(i < n_phys, i, 2 ** 30)
        return kref.pad_candidates(v, i, k, phys_largest)

    def scan(qt, pt, roffs):
        def row_step(carry, xs):
            cv, ci = carry                                   # vertical merge
            tiles, roff = xs
            v, i = tile_topk(qt, tiles, roff)
            return kref.merge_topk(cv, ci, v, i, k=k,
                                   largest=phys_largest), None

        # tile 0 seeds the tournament (its padded-slot indices are real
        # column positions, which the interpreter also reports), remaining
        # row tiles stream through the scan.
        init = tile_topk(qt, tuple(x[0] for x in pt), roffs[0])
        (v, i), _ = jax.lax.scan(
            row_step, init, (tuple(x[1:] for x in pt), roffs[1:]),
            unroll=min(unroll, max(1, pt[0].shape[0] - 1)))
        return v, i

    return scan


def _layout_queries(q, spec, packed: bool = False):
    """Encode + pad + split a query chunk into per-column-tile slabs.

    Packed: each column tile's ``dims_per_tile`` cells pack into their
    own ``ceil(dpt/32)`` uint32 lanes — tiling in **lane units** — so a
    tile's partial count covers exactly the same logical dims as the
    float slab it replaces (tail bits of a tile's last lane are zero in
    queries, patterns, and care masks alike).
    """
    gc, dpt, dim = spec.grid_cols, spec.dims_per_tile, spec.dim
    batch = q.shape[0]
    if packed:
        qb = _bits(q, spec.metric)
        qp = jnp.pad(qb, ((0, 0), (0, gc * dpt - dim)))
        return kpack.pack_bits(qp.reshape(batch, gc, dpt)).transpose(1, 0, 2)
    qe = _encode(q, spec.metric).astype(jnp.float32)
    qp = jnp.pad(qe, ((0, 0), (0, gc * dpt - dim)))
    return qp.reshape(batch, gc, dpt).transpose(1, 0, 2)     # (gc, B, dpt)


def _lay_patterns(p, care, spec, gr_total: int,
                  packed: bool) -> Tuple[jax.Array, ...]:
    """Gallery (+ care mask) laid out as per-subarray tiles.

    Returns the tuple of pattern leaves the tournament scans over:
    ``(patterns,)`` or ``(patterns, care)``, each
    ``(gr_total, gc, tile_rows, dpt-or-lanes)``.  ``gr_total`` exceeds
    ``spec.grid_rows`` only for sharded plans (shard-padding tiles).
    """
    tr, dpt, gc = spec.tile_rows, spec.dims_per_tile, spec.grid_cols
    n, dim = spec.n, spec.dim
    pad = ((0, gr_total * tr - n), (0, gc * dpt - dim))

    def lay(x):
        return x.reshape(gr_total, tr, gc, dpt).transpose(0, 2, 1, 3)

    if packed:
        pe = jnp.pad(_bits(jnp.asarray(p), spec.metric), pad)
        leaves = [kpack.pack_bits(lay(pe))]
        if care is not None:
            ce = jnp.pad(jnp.asarray(care) != 0, pad)
            leaves.append(kpack.pack_bits(lay(ce)))
        return tuple(leaves)
    pe = jnp.pad(_encode(jnp.asarray(p), spec.metric).astype(jnp.float32),
                 pad)
    leaves = [lay(pe)]
    if care is not None:
        ce = jnp.pad((jnp.asarray(care) != 0).astype(jnp.float32), pad)
        leaves.append(lay(ce))
    return tuple(leaves)


def _tile_row_update(spec, packed: bool, placement=None):
    """Row-update closure for the tile-layout executables (jnp + sharded).

    ``update(prepared, srcs, idx)`` re-lays only the row tiles touched
    by ``idx`` — running the *same* encode/pack/layout code a full
    prepare runs, on a ``len(tiles)``-tile slice — and scatters them
    into the prepared leaves.  ``srcs`` are the **post-mutation** stored
    operands, ``(gallery,)`` / ``(gallery, care)`` / ``(lo, hi)``.
    ``placement`` (sharded plans) re-pins each updated leaf to the mesh
    so every rewritten tile lands back on its owning shard.
    """
    def relay(prepared, srcs, tiles):
        # tiles has static length under jit; the jit cache retraces per
        # touched-tile count, which a retraining loop repeats constantly
        nt = tiles.shape[0]
        tspec = replace(spec, n=nt * spec.tile_rows)
        blocks = [_tile_rows_block(s, tiles, spec.tile_rows, spec.n)
                  for s in srcs]
        if isinstance(spec, SimilaritySpec):
            fresh = _lay_patterns(blocks[0],
                                  blocks[1] if len(blocks) > 1 else None,
                                  tspec, nt, packed)
        else:
            fresh = _lay_range_patterns(blocks, tspec, nt, packed)
        return tuple(leaf.at[tiles].set(f.astype(leaf.dtype))
                     for leaf, f in zip(prepared, fresh))

    # the donating variant scatters the fresh tiles into the old
    # prepared leaves' buffers in place (the caller just invalidated
    # the old layout — see update_rows(donate=True))
    relay_jit = jax.jit(relay)
    relay_don = jax.jit(relay, donate_argnums=0)

    def update(prepared, srcs, idx, donate=False):
        tiles = np.unique(np.asarray(idx, np.int64) // spec.tile_rows)
        fn = relay_don if donate else relay_jit
        out = fn(tuple(prepared), tuple(srcs), jnp.asarray(tiles, jnp.int32))
        if placement is not None:
            out = tuple(jax.device_put(x, placement) for x in out)
        return out

    return update


def _row_scatter_update(spec, packed: bool, interval: bool = False):
    """Row-update closure for the pallas executables, whose prepared
    layout is the block-padded 2-D operand itself — lane-major
    ``(lanes, rows)`` when packed: encode/pack just the touched rows and
    scatter them (padding lanes/columns stay zero)."""
    def relay(prepared, srcs, j):
        out = []
        for leaf, s in zip(prepared, srcs):
            rows = jnp.asarray(s)[j]
            if packed:
                enc = kpack.pack_bits(_bits(rows, spec.metric))
                enc = jnp.pad(enc, ((0, 0), (0, leaf.shape[0] - enc.shape[1])))
                out.append(leaf.at[:, j].set(enc.T.astype(leaf.dtype)))
                continue
            if interval:
                enc = rows.astype(jnp.float32)
            else:
                enc = _encode(rows, spec.metric).astype(jnp.float32)
            enc = jnp.pad(enc, ((0, 0), (0, leaf.shape[1] - enc.shape[1])))
            out.append(leaf.at[j].set(enc.astype(leaf.dtype)))
        return tuple(out)

    relay_jit = jax.jit(relay)
    relay_don = jax.jit(relay, donate_argnums=0)

    def update(prepared, srcs, idx, donate=False):
        fn = relay_don if donate else relay_jit
        return fn(tuple(prepared), tuple(srcs),
                  jnp.asarray(np.asarray(idx, np.int64)))

    return update


# ---------------------------------------------------------------------------
# Similarity executables
# ---------------------------------------------------------------------------


def _build_scan_executable(spec: SimilaritySpec, batch: int,
                           packed: bool = False, unroll: int = 1):
    """(prepare_patterns, chunk_fn, row_update) for the jnp
    (reference-tiled) backend.

    ``chunk_fn`` mirrors ``kernels.ref.cam_topk_tiled`` exactly — same
    partial-sum order, same stable top-k and tournament merges — but as a
    ``jax.lax.scan`` over the (row_tile, col_tile) grid, so the jaxpr
    stays small at any grid size and XLA pipelines the tiles.  With
    ``packed=True`` the same scan runs over uint32 lane tiles
    (XOR+popcount partial counts) — identical integers, 1/32nd the
    resident gallery.
    """
    _, to_logical, _ = _metric_values(spec.metric, spec.largest)
    gr, dim = spec.grid_rows, spec.dim
    scan = _tile_tournament(spec, _col_dist_fn(spec, packed), unroll)

    def prepare(p, care=None):
        return _lay_patterns(p, care, spec, gr, packed)

    def chunk_fn(q, pt):
        qt = _layout_queries(q, spec, packed)
        roffs = jnp.arange(gr, dtype=jnp.int32) * spec.tile_rows
        v, i = scan(qt, pt, roffs)
        return to_logical(v, float(dim)), i

    return jax.jit(prepare), jax.jit(chunk_fn), _tile_row_update(spec, packed)


def _dense_spec(spec):
    """The one-tile equivalent of a single-column-tile spec: the whole
    (physically padded) gallery as one ``(grid_rows * tile_rows, dim)``
    tile.  Dense and tiled execution are bit-identical for such specs —
    each row's value is one full-width distance either way, and a stable
    dense top-k selects exactly what the tile tournament's stable merges
    select — so the tiny executables simply reuse the tiled builders on
    this derived spec (including their row-update closures, whose tile
    granularity becomes "all rows").
    """
    if spec.grid_cols != 1:
        raise ValueError("dense fast path requires grid_cols == 1")
    return replace(spec, tile_rows=spec.grid_rows * spec.tile_rows,
                   grid_rows=1, dims_per_tile=spec.dim)


def _build_tiny_executable(spec: SimilaritySpec, batch: int,
                           packed: bool = False, unroll: int = 1):
    """Dense one-tile executable for tiny similarity plans.

    Small programs (ROADMAP item 5: the forest ``t32_d4`` point ran at
    0.43x of the interpreter) spend their time in per-tile ``lax.scan``
    stepping, not arithmetic; collapsing the grid into one dense tile
    removes the scan entirely while keeping the exact tournament
    semantics (see :func:`_dense_spec`).
    """
    return _build_scan_executable(_dense_spec(spec), batch, packed=packed,
                                  unroll=unroll)


def _build_sharded_executable(spec: SimilaritySpec, batch: int, shards: int,
                              packed: bool = False, unroll: int = 1):
    """(prepare_patterns, chunk_fn, row_update) sharding gallery rows
    over a device mesh.

    Device ``d`` holds row tiles ``[d*tps, (d+1)*tps)`` of the padded
    gallery (``tps = ceil(grid_rows / shards)``) and runs the *same*
    row-tile scan as the single-device executable over its shard — the
    bank level of the paper's hierarchy.  ``chunk_fn`` returns the
    per-device candidate lists still *sharded* ``(shards, batch, k)``;
    the cross-device tournament happens in :func:`merge_shard_candidates`
    at result-materialisation time.

    The per-device program deliberately contains **no collective**: an
    ``all_gather`` at the tail of each chunk would make every device's
    stream rendezvous with the slowest shard before its next chunk could
    start, serialising the pipeline exactly where the serving layer
    needs overlap.  Collective-free shard programs let each device run
    chunk after chunk back-to-back; the merge is O(shards·k) per query
    and runs off-stream.

    Padding tiles introduced by uneven division live *beyond* the
    single-device physical row count ``grid_rows * tile_rows``; their
    candidates are rewritten to the ``pad_candidates`` sentinels
    (losing value, index ``2**30``) so a sharded plan emits bit-identical
    output to the unsharded one even when ``n < k`` leaves losing slots
    visible.
    """
    _, to_logical, _ = _metric_values(spec.metric, spec.largest)
    tr, gr = spec.tile_rows, spec.grid_rows
    dim = spec.dim
    mesh = make_data_mesh(shards)
    tps = -(-gr // shards)          # row tiles per shard
    gr_pad = shards * tps
    scan = _tile_tournament(spec, _col_dist_fn(spec, packed), unroll)

    def prepare(p, care=None):
        pt = _lay_patterns(p, care, spec, gr_pad, packed)
        # lay the row-tile axis out over the mesh once, behind the plan
        # cache — chunk execution never re-shards the gallery
        sh = NamedSharding(mesh, PartitionSpec("data"))
        return tuple(jax.device_put(x, sh) for x in pt)

    def local_scan(qt, pt):
        """One device's shard of the row-tile tournament (no collectives)."""
        d = jax.lax.axis_index("data")
        roffs = (d * tps + jnp.arange(tps, dtype=jnp.int32)) * tr
        v, i = scan(qt, pt, roffs)
        # logical-domain conversion is elementwise and strictly monotone,
        # so the host-side merge can run directly on logical values with
        # the logical polarity and still match the physical tournament
        return to_logical(v, float(dim))[None], i[None]   # (1, B, k)

    def chunk_fn(q, pt):
        qt = _layout_queries(q, spec, packed)
        # PartitionSpec("data") applies prefix-wise to every pattern leaf
        return jax.shard_map(
            local_scan, mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec("data")),
            out_specs=(PartitionSpec("data"), PartitionSpec("data")),
            check_vma=False)(qt, pt)                          # (S, B, k)

    sh = NamedSharding(mesh, PartitionSpec("data"))
    return prepare, jax.jit(chunk_fn), _tile_row_update(spec, packed,
                                                        placement=sh)


def merge_shard_candidates(values: Any, indices: Any, *, k: int,
                           largest: bool) -> Tuple[Any, Any]:
    """Cross-shard top-k tournament, host-side.

    Takes the ``(shards, batch, k)`` per-device candidate lists a sharded
    ``chunk_fn`` emits and reduces them to ``(batch, k)``.  Semantically
    identical to folding :func:`kref.merge_topk` over shards in ascending
    order: concatenation in shard order is concatenation in ascending
    global-row order, and a *stable* argsort on the (negated, for
    ``largest``) values breaks ties toward the lower global index exactly
    like ``lax.top_k`` does in the on-device merges.  No arithmetic
    happens here — only selection on already-computed values — so
    integer-metric results stay bit-identical to the single-device plan.
    """
    av = np.asarray(values)
    ai = np.asarray(indices)
    s, b, kk = av.shape
    vv = np.transpose(av, (1, 0, 2)).reshape(b, s * kk)
    ii = np.transpose(ai, (1, 0, 2)).reshape(b, s * kk)
    key = -vv if largest else vv
    sel = np.argsort(key, axis=-1, kind="stable")[:, :k]
    return (np.take_along_axis(vv, sel, axis=-1),
            np.take_along_axis(ii, sel, axis=-1))


def _build_pallas_executable(spec: SimilaritySpec, batch: int,
                             packed: bool = False):
    """(prepare_patterns, chunk_fn, row_update) driving the fused
    Pallas kernels.

    Pattern encoding and block padding run once per stored array (hoisted
    behind the plan cache) instead of on every ``cam_topk`` call.  With
    ``packed=True`` the packed XOR+popcount kernel runs over uint32
    lanes (lane-blocked grid) instead of the float MXU decomposition —
    candidates are bit-identical either way.  Kernel blocks start from
    the modelled subarray (``tile_rows`` x ``dims_per_tile``) rounded to
    what the TPU tiling admits.
    """
    from ...kernels import cam_search as kcs
    from ...kernels.ops import pad_to_blocks

    metric, k = spec.metric, spec.k
    phys_metric, to_logical, phys_largest = _metric_values(metric, spec.largest)
    n, dim = spec.n, spec.dim
    ternary = spec.care_arg is not None
    k_eff = min(k, n)
    bn, bm = kcs.lane_block(spec.tile_rows, n), kcs.row_block(128, batch)
    bd = kcs.lane_block(min(spec.dims_per_tile, dim), dim)
    bl = kcs.lane_block(kpack.lanes(min(spec.dims_per_tile, dim)),
                        kpack.lanes(dim))

    def prepare(p, care=None):
        if packed:      # lane-major (lanes, rows): see fused_topk_packed
            pp = pad_to_blocks(
                kpack.pack_bits(_bits(jnp.asarray(p), metric)).T, bl, bn)
            if care is None:
                return (pp,)
            cp = pad_to_blocks(
                kpack.pack_bits(jnp.asarray(care) != 0).T, bl, bn)
            return (pp, cp)
        pe = _encode(jnp.asarray(p), metric).astype(jnp.float32)
        return (pad_to_blocks(pe, bn, bd),)

    def chunk_fn(q, pp):
        if packed:
            qp = pad_to_blocks(kpack.pack_bits(_bits(q, metric)), bm, bl)
            v, i = kcs.fused_topk_packed_pallas(
                qp, pp[0], pp[1] if ternary else None, k=k_eff,
                largest=phys_largest, n_valid=n, block_m=bm, block_n=bn,
                block_l=bl)
        else:
            qe = _encode(q, metric).astype(jnp.float32)
            qp = pad_to_blocks(qe, bm, bd)
            v, i = kcs.fused_topk_pallas(
                qp, pp[0], metric=phys_metric, k=k_eff,
                largest=phys_largest, n_valid=n, block_m=bm, block_n=bn,
                block_d=bd)
        b = q.shape[0]
        v, i = kref.pad_candidates(v[:b], i[:b], k, phys_largest)
        return to_logical(v, float(dim)), i

    return jax.jit(prepare), jax.jit(chunk_fn), _row_scatter_update(spec,
                                                                    packed)


# ---------------------------------------------------------------------------
# Range-search executables (boolean match: TH threshold / aCAM interval)
# ---------------------------------------------------------------------------


def _range_col_fn(spec: RangeSpec, packed: bool) -> Callable:
    """Per-column-tile partial value for a range program.

    Threshold mode accumulates the same physical distances the search
    path uses (packed popcounts included); interval mode accumulates
    aCAM *violation counts* — ``(q < lo) | (q > hi)`` per cell, summed.
    Both are additive over column tiles, so the scan reproduces the
    dense oracle exactly (integer counts) or in identical float order
    (eucl, mirroring :func:`kref.tiled_distances`).
    """
    if spec.mode == "interval":
        # the pinned oracle IS the per-tile function: violation counts
        # are additive over dimension tiles by construction
        return lambda qc, pr: kref.acam_violations(qc, pr[0], pr[1])
    phys_metric, _, _ = _metric_values(spec.metric, True)
    if packed:
        return lambda qc, pr: kref.packed_distances(qc, pr[0])
    return lambda qc, pr: kref.distances(qc, pr[0], phys_metric)


def _range_tile_scan(spec: RangeSpec, col_fn: Callable, unroll: int = 1):
    """Row-tile scan for range programs: ``scan(qt, pt)`` accumulates
    each row tile's physical value over the column tiles and returns
    the stacked ``(n_tiles, batch, tile_rows)`` value blocks.  No
    tournament — every stored row keeps its own match line.  Shape-
    polymorphic in the query batch, like :func:`_tile_tournament`
    (whose unroll-clamp rationale also applies here)."""
    tr = spec.tile_rows
    unroll = max(1, int(unroll))

    def tile_value(qt, pr):
        batch = qt.shape[1]

        def col_step(acc, xs):
            return acc + col_fn(xs[0], xs[1:]), None

        dist, _ = jax.lax.scan(
            col_step, jnp.zeros((batch, tr), jnp.float32), (qt, *pr),
            unroll=min(unroll, qt.shape[0]))
        return dist

    def scan(qt, pt):
        def row_step(carry, xs):
            return carry, tile_value(qt, xs)

        _, dists = jax.lax.scan(row_step, None, pt,
                                unroll=min(unroll, max(1, pt[0].shape[0])))
        return dists                                    # (gr, B, tr)

    return scan


def _range_compare(spec: RangeSpec):
    """Value block -> boolean match block, in the logical metric domain."""
    if spec.mode == "interval":
        return lambda d: d == 0
    _, to_logical, _ = _metric_values(spec.metric, True)
    tau, below, dim = spec.threshold, spec.below, float(spec.dim)
    if below:
        return lambda d: to_logical(d, dim) <= tau
    return lambda d: to_logical(d, dim) >= tau


def _lay_range_patterns(pats, spec: RangeSpec, gr_total: int,
                        packed: bool) -> Tuple[jax.Array, ...]:
    """Stored operands laid out as per-subarray tiles.

    ``(patterns,)`` or ``(lo, hi)``, each ``(gr_total, gc, tr, X)``.
    Zero padding is interval-safe: padded dims carry ``q = lo = hi =
    0`` (never a violation) and padded rows land beyond ``spec.n``,
    where finalize slices them off.
    """
    leaves = []
    for p in pats:
        leaves.extend(_lay_patterns(p, None, spec, gr_total, packed))
    return tuple(leaves)


def _build_range_scan_executable(spec: RangeSpec, batch: int,
                                 packed: bool = False, unroll: int = 1):
    """(prepare, chunk_fn, row_update) for the jnp range path: chunk_fn
    returns the ``(batch, grid_rows * tile_rows)`` boolean match block."""
    gr = spec.grid_rows
    scan = _range_tile_scan(spec, _range_col_fn(spec, packed), unroll)
    compare = _range_compare(spec)

    def prepare(*pats):
        return _lay_range_patterns(pats, spec, gr, packed)

    def chunk_fn(q, pt):
        qt = _layout_queries(q, spec, packed)
        d = scan(qt, pt)                                 # (gr, B, tr)
        hit = compare(d)
        return hit.transpose(1, 0, 2).reshape(q.shape[0], -1)

    return jax.jit(prepare), jax.jit(chunk_fn), _tile_row_update(spec, packed)


def _build_tiny_range_executable(spec: RangeSpec, batch: int,
                                 packed: bool = False, unroll: int = 1):
    """Dense one-tile executable for tiny range plans (the forest
    small-program case) — the range twin of
    :func:`_build_tiny_executable`."""
    return _build_range_scan_executable(_dense_spec(spec), batch,
                                        packed=packed, unroll=unroll)


def _build_range_sharded_executable(spec: RangeSpec, batch: int, shards: int,
                                    packed: bool = False, unroll: int = 1):
    """(prepare, chunk_fn, row_update) sharding stored rows over a
    device mesh.

    Same bank-level row split as the sharded search executable, but the
    per-device outputs are boolean match slices that simply
    *concatenate* in shard order (== ascending global row order) at
    finalize — range search has no cross-shard tournament, so the
    per-device program is trivially collective-free.
    """
    tr, gr = spec.tile_rows, spec.grid_rows
    mesh = make_data_mesh(shards)
    tps = -(-gr // shards)
    gr_pad = shards * tps
    scan = _range_tile_scan(spec, _range_col_fn(spec, packed), unroll)
    compare = _range_compare(spec)

    def prepare(*pats):
        pt = _lay_range_patterns(pats, spec, gr_pad, packed)
        sh = NamedSharding(mesh, PartitionSpec("data"))
        return tuple(jax.device_put(x, sh) for x in pt)

    def local_scan(qt, pt):
        d = scan(qt, pt)                                 # (tps, B, tr)
        hit = compare(d)
        return hit.transpose(1, 0, 2).reshape(qt.shape[1], tps * tr)[None]

    def chunk_fn(q, pt):
        qt = _layout_queries(q, spec, packed)
        return jax.shard_map(
            local_scan, mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec("data")),
            out_specs=PartitionSpec("data"),
            check_vma=False)(qt, pt)                     # (S, B, tps*tr)

    sh = NamedSharding(mesh, PartitionSpec("data"))
    return prepare, jax.jit(chunk_fn), _tile_row_update(spec, packed,
                                                        placement=sh)


def _build_range_pallas_executable(spec: RangeSpec, batch: int):
    """(prepare, chunk_fn, row_update) driving the fused aCAM /
    threshold kernels.

    The match threshold (or the ``violations == 0`` test) happens at
    block-extraction time inside the kernel — only an int8 matrix
    leaves it.  Unpacked operands only (the packed popcount path lives
    in the jnp executable).
    """
    from ...kernels import acam as kacam
    from ...kernels.cam_search import lane_block, row_block
    from ...kernels.ops import pad_to_blocks

    n, dim = spec.n, spec.dim
    bn, bm = lane_block(spec.tile_rows, n), row_block(128, batch)
    bd = lane_block(min(spec.dims_per_tile, dim), dim)
    interval = spec.mode == "interval"
    if not interval:
        phys_metric, _, _ = _metric_values(spec.metric, True)
        to_logical = "bipolar" if spec.metric in ("dot", "cos") \
            else "identity"

    def prepare(*pats):
        if interval:
            return tuple(
                pad_to_blocks(jnp.asarray(p).astype(jnp.float32), bn, bd)
                for p in pats)
        pe = _encode(jnp.asarray(pats[0]), spec.metric).astype(jnp.float32)
        return (pad_to_blocks(pe, bn, bd),)

    def chunk_fn(q, pp):
        if interval:
            qp = pad_to_blocks(q.astype(jnp.float32), bm, bd)
            hit = kacam.acam_match_pallas(
                qp, pp[0], pp[1], n_valid=n, block_m=bm, block_n=bn,
                block_d=bd)
        else:
            qe = _encode(q, spec.metric).astype(jnp.float32)
            qp = pad_to_blocks(qe, bm, bd)
            hit = kacam.range_match_pallas(
                qp, pp[0], metric=phys_metric, threshold=spec.threshold,
                below=spec.below, to_logical=to_logical, dim=dim,
                n_valid=n, block_m=bm, block_n=bn, block_d=bd)
        return hit[:q.shape[0]] != 0

    return jax.jit(prepare), jax.jit(chunk_fn), _row_scatter_update(
        spec, packed=False, interval=interval)
