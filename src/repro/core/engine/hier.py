"""Hierarchical two-stage CAM search: :class:`HierarchicalPlan`.

The CAM analogue of an IVF index, built on the plan-graph layer
(:mod:`.composite`).  ``prepare`` clusters the gallery rows with a
seeded k-means and lays each cluster out on its own group of row tiles;
at dispatch time a *coarse* :class:`~.plans.SearchPlan` over the
cluster centroids selects the ``nprobe`` most promising clusters per
query, and the *fine* probing executable searches only those clusters'
tiles — activating ``~nprobe / clusters`` of the crossbar array instead
of all of it (the paper's energy argument for hierarchical search:
match-line precharge is the dominant per-query cost, and it scales with
the number of searched subarrays).

Correctness contract
--------------------

The fine stage selects candidates by the composite key **(physical
value, global row id)** — a stable ``lax.sort`` with ``num_keys=2`` —
which is exactly the order the flat tile tournament resolves ties in
(stable per-tile ``lax.top_k`` + ascending-row-offset merges).  Row
placement inside the cluster tiles is therefore irrelevant to the
result: any probe schedule that covers the true top-k rows returns
bit-identical output to the flat plan (integer metrics; eucl keeps the
repo-wide float-tolerance contract).  Consequences:

* ``nprobe == clusters`` probes everything → bit-identical to the flat
  plan, sharded or not, packed or not.  (One dead-slot caveat: when
  fewer than k rows exist/are probed, the losing slots carry the
  ``2**30`` sentinel index here, while the flat tournament may report
  ragged in-extent positions — same losing values, geometry-dependent
  filler indices.  Winning slots always match exactly.)
* ``update_rows`` may place a moved row in *any* free slot of its new
  cluster — results match a full re-layout with the same centroids
  bit-for-bit, so the incremental path needs no compensation logic.
* Centroids are **fixed** across ``update_rows`` (k-means runs once per
  prepared gallery).  A mutated row is reassigned to its nearest stored
  centroid; if its new cluster's tiles are full, the whole layout is
  rebuilt (same centroids, fresh uniform tiles-per-cluster).

Sharding splits the *fine tile axis* over the device mesh: each device
holds ``1/shards`` of the cluster tiles and probes only the candidate
tiles it owns (gathers into its local shard; foreign candidates mask to
sentinels).  Per-device candidate lists merge host-side by the same
composite key (:func:`_merge_hier_shards` — a lexsort, because probing
order is not ascending-row order like the flat shard merge).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...launch.mesh import make_data_mesh
from ...obs.trace import trace_span, tracer
from ..envcfg import env_int
from .base import _pick_batch, _size
from .cache import _lookup_or_insert, _normalize_shards, get_plan
from .composite import CompositePlan, HierarchicalSpec
from .executables import _lay_patterns, _layout_queries
from .plans import SearchPlan
from .spec import (SimilaritySpec, _PACKABLE_METRICS, _bits, _metric_values,
                   _resolve_pack, extract_plan_spec, module_for_spec)

__all__ = ["HierarchicalPlan", "get_hierarchical_plan"]

#: sentinel global row id for empty tile slots / losing candidates —
#: the same value the flat executables' ``pad_candidates`` emits, so a
#: hierarchical result is indistinguishable from a flat one
_SENT = 2 ** 30


# ---------------------------------------------------------------------------
# Clustering (host-driven, jnp matmuls): seeded k-means + assignment
# ---------------------------------------------------------------------------


def _enc_f32(x, metric: str) -> jax.Array:
    """Rows in the clustering space: cell bits (as {0,1} float32) for
    the packable metrics — their physical search is Hamming on bits —
    raw float32 values for eucl."""
    if metric in _PACKABLE_METRICS:
        return _bits(jnp.asarray(x), metric).astype(jnp.float32)
    return jnp.asarray(x).astype(jnp.float32)


def _argmin_assign(rows: jax.Array, cent: jax.Array, metric: str) -> jax.Array:
    """Nearest stored centroid per row, ties to the lower centroid id.

    Distances via matmul (fast at gallery scale): Hamming between bit
    vectors is ``b @ (1-c)^T + (1-b) @ c^T`` — exact integers in f32 —
    and eucl uses the same expansion as the kernels.  ``argmin`` picks
    the first minimum, which makes assignment deterministic.
    """
    if metric in _PACKABLE_METRICS:
        d = rows @ (1.0 - cent).T + (1.0 - rows) @ cent.T
    else:
        qq = (rows * rows).sum(-1, keepdims=True)
        cc = (cent * cent).sum(-1)
        d = qq + cc[None, :] - 2.0 * (rows @ cent.T)
    return jnp.argmin(d, axis=1).astype(jnp.int32)


def _assign_rows(rows_raw, cent_src, metric: str) -> jax.Array:
    """Assignment of raw-domain rows against the stored raw-domain
    centroids (used by ``update_rows`` reassignment)."""
    return _argmin_assign(_enc_f32(rows_raw, metric),
                          _enc_f32(cent_src, metric), metric)


def _kmeans(g: jax.Array, spec_h: HierarchicalSpec
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded Lloyd k-means over the encoded gallery.

    Returns ``(centroids, assign)`` — centroids in the *raw input
    domain* (binarised {0,1} cells for the packable metrics, float
    means for eucl) so they can be stored directly as the coarse
    plan's gallery, and the final per-row cluster assignment.
    Deterministic: seeded init (distinct rows), first-minimum ties,
    mean-threshold binarisation; empty clusters keep their previous
    centroid.
    """
    fine = spec_h.fine
    n, clusters = fine.n, spec_h.clusters
    metric = fine.metric
    enc = _enc_f32(g, metric)
    rng = np.random.default_rng(spec_h.seed)
    cent = enc[jnp.asarray(rng.choice(n, size=clusters, replace=False))]
    ones = jnp.ones((n,), jnp.float32)
    binary = metric in _PACKABLE_METRICS
    for _ in range(spec_h.kmeans_iters):
        a = _argmin_assign(enc, cent, metric)
        sums = jax.ops.segment_sum(enc, a, num_segments=clusters)
        cnt = jax.ops.segment_sum(ones, a, num_segments=clusters)
        mean = sums / jnp.maximum(cnt, 1.0)[:, None]
        newc = (mean > 0.5).astype(jnp.float32) if binary else mean
        cent = jnp.where((cnt > 0.0)[:, None], newc, cent)
    a = _argmin_assign(enc, cent, metric)
    return np.asarray(cent), np.asarray(a, np.int32)


# ---------------------------------------------------------------------------
# Layout: cluster assignment -> per-cluster tile groups
# ---------------------------------------------------------------------------


def _layout_from_assign(assign: np.ndarray, clusters: int, tr: int,
                        n: int) -> Tuple[np.ndarray, np.ndarray, int,
                                         np.ndarray]:
    """Uniform tiles-per-cluster slot layout from an assignment.

    Every cluster gets ``tpc = ceil(max_cluster_size / tile_rows)``
    tiles (uniform so a probe step is a static-shape gather: candidate
    tile ids are just ``cluster * tpc + j``).  Rows land in their
    cluster's slots in ascending global-id order; empty slots carry the
    ``_SENT`` row id.  Returns ``(row_ids (T, tr), slot_of (n,), tpc,
    cnt (clusters,))`` where ``cnt[c]`` is the *occupied tile prefix*
    of cluster ``c`` — k-means clusters are imbalanced, so most
    clusters fill far fewer than ``tpc`` tiles and the probe skips the
    all-sentinel remainder (see :func:`_probe_budget`).
    """
    counts = np.bincount(assign, minlength=clusters)
    tpc = max(1, int(-(-int(counts.max()) // tr))) if n else 1
    cap = tpc * tr
    flat = np.full(clusters * cap, _SENT, np.int32)
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    pos = np.arange(n, dtype=np.int64) - starts[assign[order]]
    slot = assign[order].astype(np.int64) * cap + pos
    flat[slot] = order.astype(np.int32)
    slot_of = np.empty(n, np.int64)
    slot_of[order] = slot
    cnt = (-(-counts // tr)).astype(np.int32)       # rows fill a prefix
    return flat.reshape(clusters * tpc, tr), slot_of, tpc, cnt


def _probe_budget(cnt: np.ndarray, nprobe: int, tpc: int) -> int:
    """Static per-query probe-step budget: the worst case any query can
    need is the ``nprobe`` largest occupied-tile prefixes — data
    dependent on the *gallery* (known at prepare time), never on the
    queries, so the probe jit stays query-shape-static.  Rounded up to
    a multiple of 16 steps so small occupancy drift under
    ``update_rows`` does not retrace, capped at the padded
    ``nprobe * tpc`` it replaces.

    Trace-motivated (ROADMAP item 1): at the bench geometry the
    largest cluster forces ``tpc = 26`` while the mean occupancy is
    ~8.5 tiles, so the padded schedule ran 416 probe steps/query where
    the top-16 occupancy sum needs 235 — the gather, distance and
    select stages all shrank proportionally (probe 191 ms -> 99 ms,
    bit-identical output).
    """
    top = np.sort(cnt)[::-1][:nprobe]
    nb = int(top.sum())
    nb = -(-max(nb, 1) // 16) * 16
    return max(1, min(nprobe * tpc, nb))


def _leaves_from_rows(g: jax.Array, row_ids: np.ndarray,
                      fine: SimilaritySpec, packed: bool) -> Tuple:
    """Fine tile leaves from the slot layout: gather rows by slot
    (empty slots become zero rows, which every cell encoding preserves)
    and run the standard pattern layout on the permuted gallery."""
    t, tr = row_ids.shape
    flat = jnp.asarray(row_ids.reshape(-1))
    valid = flat < _SENT
    rows = jnp.asarray(g)[jnp.clip(flat, 0, fine.n - 1)]
    rows = jnp.where(valid[:, None], rows, 0)
    lspec = replace(fine, n=t * tr, grid_rows=t)
    return _lay_patterns(rows, None, lspec, t, packed)


@dataclass
class HierState:
    """A prepared hierarchical gallery (one pattern-memo entry).

    Device state: the centroid gallery + its coarse-prepared leaves,
    the fine tile leaves and the device slot->row-id map.  Host state:
    the assignment / slot bookkeeping ``update_rows`` rewrites (master
    copies — the incremental path copies before mutating so an older
    memo entry never sees a newer layout).
    """

    centroid_src: jax.Array            # (clusters, dim) raw-domain
    coarse_prepared: Any               # coarse plan's prepared leaves
    leaves: Tuple[jax.Array, ...]      # ((T[+pad], gc, tr, X),)
    row_ids: jax.Array                 # (T[+pad], tr) int32, device
    assign: np.ndarray                 # (n,) int32
    slot_of: np.ndarray                # (n,) int64 flat slot index
    row_ids_h: np.ndarray              # (T, tr) int32, host master
    tpc: int                           # tiles per cluster
    cnt: jax.Array                     # (clusters,) occupied tile prefix
    cnt_h: np.ndarray                  # host master of ``cnt``
    budget: int                        # static probe steps per query


# ---------------------------------------------------------------------------
# Fine probing executables
# ---------------------------------------------------------------------------


def _batched_col_dist(fine: SimilaritySpec, packed: bool):
    """Per-query-tile partial distance: ``f(qc, pt) -> (B, tr)`` where
    *each query has its own tile* (``pt``: (B, tr, X)).  Same arithmetic
    as the flat per-tile kernels — broadcast mismatch counts / packed
    popcounts are exact integers, eucl uses the identical expansion —
    so the probed values equal the flat tournament's values.
    """
    phys_metric, _, _ = _metric_values(fine.metric, fine.largest)
    if packed:
        from ...kernels.packing import popcount32

        def fp(qc, pt):
            return popcount32(qc[:, None, :] ^ pt).sum(-1) \
                .astype(jnp.float32)
        return fp
    if phys_metric == "hamming":
        return lambda qc, pt: (qc[:, None, :] != pt).sum(-1) \
            .astype(jnp.float32)

    def fe(qc, pt):
        qq = (qc * qc).sum(-1)
        pp = (pt * pt).sum(-1)
        return qq[:, None] + pp - 2.0 * jnp.einsum("bd,btd->bt", qc, pt)
    return fe


#: per-group gather budget (array elements): probe steps are grouped so
#: one composite-key sort covers many candidate tiles — one tile per
#: sort is launch/sort-overhead-bound and loses the probing win — while
#: the gathered group buffer stays bounded (~64 MB at 4 B/element)
_GROUP_BUDGET = 1 << 24

#: gid width (rows) under which the top-k fast path may encode row ids
#: as float32 exactly (24-bit mantissa; the ``2**30`` sentinel is a
#: power of two and stays exact too)
_TOPK_GID_EXACT = 1 << 24


def _composite_select(k: int, lose, exact_gids: bool):
    """``select(skeys, gids, vals) -> (k smallest by (skey, gid))``.

    The reference implementation is one stable two-key ``lax.sort`` —
    but a full-width variadic sort is the single most expensive op in
    the probe (slower than the whole flat scan at bench geometry).  When
    every gid is float32-exact the same selection runs as two
    ``lax.top_k`` passes + one 3k-wide cleanup sort:

    * pass 1 (top-k on the scalar key) covers every entry *strictly*
      better than the k-th smallest key ``tau`` — at most k-1 of them,
      so none is lost to top-k's positional tie-break;
    * pass 2 (top-k on ``-gid`` where ``skey == tau``) picks the
      smallest-gid entries at ``tau``, exactly the composite order;
    * entries dropped from pass 1 (``skey == tau``, wrong tie choice)
      mask to the sentinel triple and the survivors merge in one tiny
      stable sort.

    ``tau`` must come from a *reduction* over the top-k values, never a
    slice: ``nv[:, k-1:k]`` folds into top_k's internal sort+slice
    pattern and stops XLA's TopK rewrite from firing on CPU (a ~50x
    regression back to the full sort).
    """
    def by_sort(ks, kg, kd):
        ks, kg, kd = jax.lax.sort((ks, kg, kd), dimension=-1,
                                  is_stable=True, num_keys=2)
        return ks[:, :k], kg[:, :k], kd[:, :k]

    if not exact_gids:
        return by_sort

    def by_topk(ks, kg, kd):
        nv, idx = jax.lax.top_k(-ks, k)
        tau = -jnp.min(nv, axis=-1, keepdims=True)
        sk = jnp.take_along_axis(ks, idx, axis=-1)
        sg = jnp.take_along_axis(kg, idx, axis=-1)
        sv = jnp.take_along_axis(kd, idx, axis=-1)
        strict = sk < tau
        sk = jnp.where(strict, sk, jnp.inf)
        sg = jnp.where(strict, sg, _SENT)
        sv = jnp.where(strict, sv, lose)
        gf = kg.astype(jnp.float32)
        tv, tidx = jax.lax.top_k(jnp.where(ks == tau, -gf, -jnp.inf), k)
        tie = tv > -jnp.inf
        tk = jnp.where(tie, jnp.broadcast_to(tau, tv.shape), jnp.inf)
        tg = jnp.where(tie, (-tv).astype(jnp.int32), _SENT)
        tvv = jnp.where(tie, jnp.take_along_axis(kd, tidx, axis=-1), lose)
        return by_sort(jnp.concatenate([sk, tk], axis=-1),
                       jnp.concatenate([sg, tg], axis=-1),
                       jnp.concatenate([sv, tvv], axis=-1))

    return by_topk


def _step_to_tile(s, pre, nprobe: int):
    """Map one probe step to its per-query (probe rank, tile offset).

    ``pre`` (B, nprobe+1) is the per-query inclusive prefix sum of the
    probed clusters' occupied-tile counts: step ``s`` belongs to the
    probe rank whose prefix window contains it, at offset ``s`` minus
    the window start.  Steps past ``pre[:, -1]`` are dead padding (the
    static budget covers the worst-case query; most need fewer).
    """
    p = jnp.sum(s >= pre[:, 1:], axis=1)
    p = jnp.minimum(p, nprobe - 1)
    j = s - jnp.take_along_axis(pre, p[:, None], axis=1)[:, 0]
    live = s < pre[:, -1]
    return p, j, live


def _probe_prefix(ci, cnt):
    """Per-query prefix sums of the probed clusters' occupied-tile
    counts: ``(B, nprobe+1)`` int32, leading zero column."""
    pc = cnt[ci]
    return jnp.concatenate(
        [jnp.zeros((ci.shape[0], 1), jnp.int32),
         jnp.cumsum(pc, axis=1, dtype=jnp.int32)], axis=1)


def _probe_steps(spec_h: HierarchicalSpec, packed: bool):
    """The candidate-tile scan shared by the single-device and sharded
    probes: ``steps(qt, gather, bsz, total)`` folds ``total`` probe
    steps (the occupancy budget from :func:`_probe_budget`), where
    ``gather(s) -> (tile_leaf (B, gc, tr, X), row_ids (B, tr))`` is the
    backend-specific candidate fetch.

    Steps run in *groups*: each ``lax.scan`` iteration gathers ``G``
    candidate tiles per query and folds all ``G * tile_rows``
    candidates through one composite-key selection (physical value,
    global row id — the flat tournament's tie order, see
    :func:`_composite_select`) truncated to k.  ``G`` is the largest group whose gathered slab fits
    ``_GROUP_BUDGET`` elements, so small plans collapse to a single
    sort while huge ones keep bounded memory.  Padded trailing steps
    (when the group size does not divide the step count) mask their
    row ids to the sentinel, never duplicating a candidate.
    """
    fine = spec_h.fine
    _, _, phys_largest = _metric_values(fine.metric, fine.largest)
    tr, k = fine.tile_rows, fine.k
    lose = -jnp.inf if phys_largest else jnp.inf
    col = _batched_col_dist(fine, packed)
    select = _composite_select(k, lose, fine.n < _TOPK_GID_EXACT)
    #: per-column slab width (elements) of one gathered tile row
    wpr = fine.grid_cols * (-(-fine.dims_per_tile // 32) if packed
                            else fine.dims_per_tile)

    def run(qt, gather, bsz, total):
        per_tile = max(1, bsz * tr * wpr)
        g = max(1, min(total, _GROUP_BUDGET // per_tile))
        ngroups = -(-total // g)
        steps = jnp.arange(ngroups * g).reshape(ngroups, g)

        init = (jnp.full((bsz, k), jnp.inf, jnp.float32),
                jnp.full((bsz, k), _SENT, jnp.int32),
                jnp.full((bsz, k), lose, jnp.float32))

        def tile_dist(pt):                       # (B, gc, tr, X) -> (B, tr)
            def cstep(acc, xs):
                return acc + col(xs[0], xs[1]), None

            d, _ = jax.lax.scan(cstep, jnp.zeros((bsz, tr), jnp.float32),
                                (qt, pt.transpose(1, 0, 2, 3)))
            return d

        def step(carry, ss):                     # ss: (G,) step indices
            pt, rg = jax.vmap(gather)(ss)        # (G,B,gc,tr,X), (G,B,tr)
            rg = jnp.where((ss < total)[:, None, None], rg, _SENT)
            dist = jax.vmap(tile_dist)(pt)       # (G, B, tr)
            dist = dist.transpose(1, 0, 2).reshape(bsz, -1)
            rg = rg.transpose(1, 0, 2).reshape(bsz, -1)
            valid = rg < _SENT
            sk = jnp.where(valid, -dist if phys_largest else dist, jnp.inf)
            dd = jnp.where(valid, dist, lose)
            return select(jnp.concatenate([carry[0], sk], axis=-1),
                          jnp.concatenate([carry[1], rg], axis=-1),
                          jnp.concatenate([carry[2], dd], axis=-1)), None

        (_, kg, kd), _ = jax.lax.scan(step, init, steps)
        return kd, kg

    return run


def _hier_probe(spec_h: HierarchicalSpec, packed: bool):
    """Single-device fine probe: ``probe(q, ci, leaf, rid, cnt, tpc,
    budget)`` -> logical ``(values, indices)``.  ``tpc`` and ``budget``
    are static (the jit retraces when an overflow re-layout changes the
    tiles-per-cluster, or occupancy drift moves the bucketed budget).

    Each step probes one *occupied* tile of one probed cluster: the
    per-query prefix map (:func:`_step_to_tile`) packs the ragged
    per-cluster tile lists into a dense static schedule, so imbalanced
    clusters no longer pay the padded worst case.
    """
    fine = spec_h.fine
    nprobe = spec_h.nprobe
    _, to_logical, _ = _metric_values(fine.metric, fine.largest)
    run = _probe_steps(spec_h, packed)

    def probe(q, ci, leaf, rid, cnt, tpc, budget):
        qt = _layout_queries(q, fine, packed)
        pre = _probe_prefix(ci, cnt)

        def gather(s):
            p, j, live = _step_to_tile(s, pre, nprobe)
            c = jnp.take_along_axis(ci, p[:, None], axis=1)[:, 0]
            tile = jnp.clip(c * tpc + j, 0, leaf.shape[0] - 1)
            rg = jnp.where(live[:, None], rid[tile], _SENT)
            return leaf[tile], rg

        kd, kg = run(qt, gather, q.shape[0], budget)
        return to_logical(kd, float(fine.dim)), kg

    return jax.jit(probe, static_argnums=(5, 6))


def _hier_probe_sharded(spec_h: HierarchicalSpec, packed: bool,
                        shards: int, mesh):
    """Sharded fine probe: the tile axis lives on the mesh, each device
    gathers only the candidate tiles it owns (foreign candidates mask to
    sentinels) and emits its own (B, k) candidate list; the cross-shard
    composite-key merge happens host-side in :func:`_merge_hier_shards`."""
    fine = spec_h.fine
    nprobe = spec_h.nprobe
    _, to_logical, _ = _metric_values(fine.metric, fine.largest)
    run = _probe_steps(spec_h, packed)

    def probe(q, ci, leaf, rid, cnt, tpc, budget):
        qt = _layout_queries(q, fine, packed)
        bsz = q.shape[0]

        def local(qt_l, ci_l, leaf_l, rid_l, cnt_l):
            d = jax.lax.axis_index("data")
            tps = leaf_l.shape[0]
            pre = _probe_prefix(ci_l, cnt_l)

            def gather(s):
                p, j, live = _step_to_tile(s, pre, nprobe)
                c = jnp.take_along_axis(ci_l, p[:, None], axis=1)[:, 0]
                tile = c * tpc + j
                loc = tile - d * tps
                inr = live & (loc >= 0) & (loc < tps)
                locc = jnp.clip(loc, 0, tps - 1)
                rg = jnp.where(inr[:, None], rid_l[locc], _SENT)
                return leaf_l[locc], rg

            kd, kg = run(qt_l, gather, bsz, budget)
            return to_logical(kd, float(fine.dim))[None], kg[None]

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec(),
                      PartitionSpec("data"), PartitionSpec("data"),
                      PartitionSpec()),
            out_specs=(PartitionSpec("data"), PartitionSpec("data")),
            check_vma=False)(qt, ci, leaf, rid, cnt)         # (S, B, k)

    return jax.jit(probe, static_argnums=(5, 6))


def _merge_hier_shards(values, indices, *, k: int,
                       largest: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Cross-shard composite-key merge for hierarchical candidates.

    Unlike :func:`~.executables.merge_shard_candidates` (where shard
    order *is* ascending global-row order, so a stable value sort
    suffices), hierarchical shards hold permuted rows — the tie-break
    must be the explicit global row id.  A lexsort on (value key, row
    id) reproduces the flat tournament's selection exactly; no
    arithmetic happens, so integer-metric results stay bit-identical.
    """
    av = np.asarray(values)
    ai = np.asarray(indices)
    s, b, kk = av.shape
    vv = np.transpose(av, (1, 0, 2)).reshape(b, s * kk)
    ii = np.transpose(ai, (1, 0, 2)).reshape(b, s * kk)
    key = -vv if largest else vv
    sel = np.lexsort((ii, key), axis=-1)[:, :k]
    return (np.take_along_axis(vv, sel, axis=-1),
            np.take_along_axis(ii, sel, axis=-1))


# ---------------------------------------------------------------------------
# Executable builder: (prepare, chunk_fn, row_update)
# ---------------------------------------------------------------------------


def _build_hier_executable(spec_h: HierarchicalSpec, coarse: SearchPlan,
                           batch: int, shards: int, packed: bool):
    """The hierarchical (prepare, chunk_fn, row_update) triple.

    ``prepare`` runs host-side (k-means + layout are data-dependent
    host work; it executes once per gallery behind the pattern memo).
    ``chunk_fn`` composes the coarse plan's jitted chunk executable
    with the jitted fine probe — two async device calls, no host
    synchronisation between the stages.  ``row_update`` is the
    reassigning incremental relay described on :class:`HierState`.
    """
    fine = spec_h.fine
    tr = fine.tile_rows
    mesh = make_data_mesh(shards) if shards > 1 else None
    placement = NamedSharding(mesh, PartitionSpec("data")) if mesh else None
    probe = _hier_probe_sharded(spec_h, packed, shards, mesh) if mesh \
        else _hier_probe(spec_h, packed)

    def materialise(g, row_h):
        """Device leaves + device row-id map from a host slot layout."""
        leaves = _leaves_from_rows(g, row_h, fine, packed)
        if placement is None:
            return leaves, jnp.asarray(row_h)
        t = row_h.shape[0]
        tps = -(-t // shards)
        pad_t = shards * tps - t
        if pad_t:
            leaves = tuple(
                jnp.pad(x, ((0, pad_t),) + ((0, 0),) * (x.ndim - 1))
                for x in leaves)
        rid = np.full((shards * tps, tr), _SENT, np.int32)
        rid[:t] = row_h
        return (tuple(jax.device_put(x, placement) for x in leaves),
                jax.device_put(jnp.asarray(rid), placement))

    def fresh_state(g, cent_src, cpp, assign):
        row_h, slot_of, tpc, cnt_h = _layout_from_assign(
            assign, spec_h.clusters, tr, fine.n)
        leaves, rid = materialise(g, row_h)
        return HierState(centroid_src=cent_src, coarse_prepared=cpp,
                         leaves=leaves, row_ids=rid, assign=assign,
                         slot_of=slot_of, row_ids_h=row_h, tpc=tpc,
                         cnt=jnp.asarray(cnt_h), cnt_h=cnt_h,
                         budget=_probe_budget(cnt_h, spec_h.nprobe, tpc))

    def prepare(gallery):
        g = jnp.asarray(gallery)
        cent, assign = _kmeans(g, spec_h)
        cent_src = jnp.asarray(cent)
        cpp = coarse._prepared_patterns(cent_src)
        return fresh_state(g, cent_src, cpp, assign)

    def chunk_fn(q, hs):
        # under tracing each stage blocks on its device result so the
        # span durations attribute real stage time instead of jax's
        # async dispatch latency (the stages are data-dependent anyway,
        # so blocking costs pipelining only across chunk boundaries)
        with trace_span("hier.coarse"):
            _, ci = coarse._chunk_fn(q, hs.coarse_prepared)
            if tracer.enabled:
                ci.block_until_ready()
        with trace_span("hier.probe",
                        args=None if not tracer.enabled else
                        {"budget": hs.budget, "tpc": hs.tpc}):
            out = probe(q, ci, hs.leaves[0], hs.row_ids, hs.cnt,
                        hs.tpc, hs.budget)
            if tracer.enabled:
                jax.block_until_ready(out)
            return out

    # -- incremental row update -------------------------------------------

    def relay(leaves, rid, g, tiles):
        """Re-lay the touched tiles from the (mutated) gallery through
        the *new* slot map and scatter them into the prepared leaves —
        the same encode/pack/layout code a full prepare runs, on a
        ``len(tiles)``-tile slice (static length: retraces per touched
        tile count, like the flat relay)."""
        nt = tiles.shape[0]
        rg = rid[tiles].reshape(-1)
        valid = rg < _SENT
        rows = jnp.asarray(g)[jnp.clip(rg, 0, fine.n - 1)]
        rows = jnp.where(valid[:, None], rows, 0)
        lspec = replace(fine, n=nt * tr, grid_rows=nt)
        fresh = _lay_patterns(rows, None, lspec, nt, packed)
        return tuple(x.at[tiles].set(f.astype(x.dtype))
                     for x, f in zip(leaves, fresh))

    relay_jit = jax.jit(relay)
    relay_don = jax.jit(relay, donate_argnums=0)

    def rid_device(row_h):
        if placement is None:
            return jnp.asarray(row_h)
        t = row_h.shape[0]
        tps = -(-t // shards)
        rid = np.full((shards * tps, tr), _SENT, np.int32)
        rid[:t] = row_h
        return jax.device_put(jnp.asarray(rid), placement)

    def row_update(hs, new_srcs, idx, donate=False):
        g_new = jnp.asarray(new_srcs[0])
        idxa = np.asarray(idx, np.int64)
        a_new = np.asarray(_assign_rows(g_new[jnp.asarray(idxa)],
                                        hs.centroid_src, fine.metric),
                           np.int32)
        assign = hs.assign.copy()
        slot_of = hs.slot_of.copy()
        row_h = hs.row_ids_h.copy()
        flat = row_h.reshape(-1)
        cap = hs.tpc * tr
        touched = set((slot_of[idxa] // tr).tolist())
        moved_clusters = set()
        overflow = False
        for r, c_new in zip(idxa.tolist(), a_new.tolist()):
            c_old = int(assign[r])
            if c_new == c_old:
                continue                      # content change, same cluster
            s_old = int(slot_of[r])
            flat[s_old] = _SENT               # vacate the old slot
            seg = flat[c_new * cap:(c_new + 1) * cap]
            free = np.flatnonzero(seg == _SENT)
            if free.size == 0:
                overflow = True
                break
            s_new = c_new * cap + int(free[0])
            flat[s_new] = r
            slot_of[r] = s_new
            assign[r] = c_new
            touched.add(s_old // tr)
            touched.add(s_new // tr)
            moved_clusters.add(c_old)
            moved_clusters.add(c_new)
        if overflow:
            # the moved row's cluster is full: rebuild the whole layout
            # with the SAME centroids and a fresh uniform tpc.  Slot
            # placement is result-irrelevant (composite-key selection),
            # so this stays bit-identical to the incremental path.
            fresh_assign = hs.assign.copy()
            fresh_assign[idxa] = a_new
            return fresh_state(g_new, hs.centroid_src, hs.coarse_prepared,
                               fresh_assign)
        rid = rid_device(row_h)
        tiles = jnp.asarray(sorted(touched), jnp.int32)
        fn = relay_don if donate else relay_jit
        leaves = fn(tuple(hs.leaves), rid, g_new, tiles)
        if placement is not None:
            leaves = tuple(jax.device_put(x, placement) for x in leaves)
        # occupancy maintenance: a moved row can extend its new
        # cluster's occupied prefix or (with holes filled later) let an
        # old one shrink — recompute the prefix for touched clusters
        # from the highest occupied slot, so probing [0, cnt) always
        # covers every live row
        cnt_h, cnt, budget = hs.cnt_h, hs.cnt, hs.budget
        if moved_clusters:
            cnt_h = cnt_h.copy()
            for c in moved_clusters:
                occ = np.flatnonzero(flat[c * cap:(c + 1) * cap] != _SENT)
                cnt_h[c] = 0 if occ.size == 0 else int(occ[-1]) // tr + 1
            cnt = jnp.asarray(cnt_h)
            budget = _probe_budget(cnt_h, spec_h.nprobe, hs.tpc)
        return HierState(centroid_src=hs.centroid_src,
                         coarse_prepared=hs.coarse_prepared,
                         leaves=leaves, row_ids=rid, assign=assign,
                         slot_of=slot_of, row_ids_h=row_h, tpc=hs.tpc,
                         cnt=cnt, cnt_h=cnt_h, budget=budget)

    return prepare, chunk_fn, row_update


# ---------------------------------------------------------------------------
# The plan and its cached factory
# ---------------------------------------------------------------------------


@dataclass
class HierarchicalPlan(CompositePlan):
    """Two-stage coarse→fine search plan (see the module docstring).

    ``stages[0]`` is the coarse centroid :class:`~.plans.SearchPlan`.
    The public surface matches :class:`~.plans.SearchPlan` — same
    ``execute`` / ``dispatch`` / ``finalize`` / ``update_rows``
    signatures, same ``(values, indices)`` results — so the serving and
    hardening layers treat it as just another plan.
    """

    family: str = field(default="hierarchical", repr=False)

    @property
    def coarse(self) -> SearchPlan:
        return self.stages[0]

    def _stored_sources(self, inputs) -> Tuple:
        return (inputs[self.spec.pattern_arg],)

    def finalize(self, pending):
        """SearchPlan-shaped finalize with the hierarchical shard merge
        (composite-key lexsort instead of the shard-order value sort)."""
        with trace_span("plan.finalize"):
            return self._finalize(pending)

    def _finalize(self, pending):
        spec = self.spec
        xp = np if self.shards > 1 else jnp
        vs, is_ = [], []
        for v, i, valid in pending.chunks:
            if self.shards > 1:
                v, i = _merge_hier_shards(v, i, k=spec.k,
                                          largest=spec.largest)
            vs.append(v[:valid])
            is_.append(i[:valid])
        if not vs:      # zero queries: well-shaped empty result
            vs = [xp.zeros((0, spec.k), xp.float32)]
            is_ = [xp.zeros((0, spec.k), xp.int32)]
        v = vs[0] if len(vs) == 1 else xp.concatenate(vs, axis=0)
        i = is_[0] if len(is_) == 1 else xp.concatenate(is_, axis=0)
        m, lead, k = pending.m, pending.lead, spec.k
        if m * k == _size(spec.out_v_shape):
            v = v.reshape(spec.out_v_shape)
            i = i.reshape(spec.out_i_shape)
        else:
            v = v.reshape(lead + (k,))
            i = i.reshape(lead + (k,))
        return (v, i)

    def update_rows(self, gallery, indices, new_rows, care=None, *,
                    donate: bool = False):
        """Row-granular gallery mutation with cluster reassignment.

        Same contract as :meth:`~.plans.SearchPlan.update_rows`
        (returns the mutated gallery; incremental memo rewrite when the
        old layout is memoised; ``donate`` reuses buffers), plus the
        hierarchical semantics documented on the module: each touched
        row is re-assigned to its nearest *stored* centroid, moving
        between cluster tile groups when needed — bit-identical to a
        full re-layout with the same centroids.
        """
        if care is not None:
            raise ValueError("hierarchical plans have no care operand")
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        self._validate_update(idx, new_rows)
        upd = self._mutate_stored((gallery,), (new_rows,), idx, donate)
        return upd[0]


def _default_clusters(fine: SimilaritySpec) -> int:
    """``~sqrt(n)`` centroids (the classic IVF balance point), never
    more than the number of row tiles (a cluster below one tile of rows
    wastes probe steps) and never more than n."""
    est = max(2, int(round(math.sqrt(fine.n))))
    est = min(est, max(1, fine.n // fine.tile_rows))
    return max(1, min(est, fine.n))


def _coarse_spec(spec_h: HierarchicalSpec) -> SimilaritySpec:
    """The coarse stage's spec: top-``nprobe`` centroids under the fine
    metric *and polarity* (a largest=True fine search wants the
    farthest clusters), same column geometry as the fine spec."""
    fine = spec_h.fine
    c = spec_h.clusters
    tr = min(fine.tile_rows, c)
    return SimilaritySpec(
        metric=fine.metric, k=spec_h.nprobe, largest=fine.largest,
        tile_rows=tr, dims_per_tile=fine.dims_per_tile,
        grid_rows=-(-c // tr), grid_cols=fine.grid_cols,
        m=fine.m, n=c, dim=fine.dim, query_arg=0, pattern_arg=1,
        out_v_shape=(fine.m, spec_h.nprobe),
        out_i_shape=(fine.m, spec_h.nprobe))


def get_hierarchical_plan(program, *, clusters: Optional[int] = None,
                          nprobe: Optional[int] = None,
                          backend: str = "jnp",
                          batch: Optional[int] = None,
                          shards: Optional[int] = None,
                          pack: Optional[bool] = None,
                          kmeans_iters: int = 8,
                          seed: int = 0) -> Optional[HierarchicalPlan]:
    """Hierarchical plan for a similarity program, from the shared cache.

    ``program`` is a partitioned similarity :class:`~..ir.Module`, its
    :class:`~.spec.SimilaritySpec`, or an existing
    :class:`HierarchicalSpec` (whose clustering fields serve as the
    defaults).  Returns ``None`` for modules that are not pure
    similarity programs, mirroring ``get_plan``.

    ``clusters`` defaults to ``~sqrt(n)`` (capped at the row-tile
    count); ``nprobe`` defaults to ``REPRO_HIER_NPROBE`` when set, else
    ``clusters // 8``.  Both clamp into valid range (``nprobe <=
    clusters <= n``).  The coarse centroid plan is itself a cached
    :class:`~.plans.SearchPlan`; the hierarchical plan is one entry in
    the same process-wide cache, keyed by its frozen
    :class:`~.composite.HierarchicalSpec` (clustering parameters
    included — different ``clusters``/``nprobe``/``seed`` are different
    result contracts, so they must not share an executable).

    Restrictions: jnp backend only (the probing stage is a gather-heavy
    scan with no fused kernel yet) and no ternary programs.
    """
    if isinstance(program, HierarchicalSpec):
        fine = program.fine
        clusters = program.clusters if clusters is None else clusters
        nprobe = program.nprobe if nprobe is None else nprobe
        kmeans_iters = program.kmeans_iters
        seed = program.seed
    elif isinstance(program, SimilaritySpec):
        fine = program
    else:
        try:
            fine = extract_plan_spec(program)
        except Exception:
            fine = None
        if fine is None:
            return None
    if backend != "jnp":
        raise ValueError(
            f"hierarchical plans require the 'jnp' backend, got {backend!r}")
    if fine.care_arg is not None:
        raise ValueError("hierarchical search does not support ternary "
                         "(care-masked) programs")
    if clusters is None:
        clusters = _default_clusters(fine)
    clusters = max(1, min(int(clusters), fine.n))
    if nprobe is None:
        nprobe = env_int("REPRO_HIER_NPROBE", 0, min_value=0) or \
            max(1, clusters // 8)
    nprobe = max(1, min(int(nprobe), clusters))
    spec_h = HierarchicalSpec(fine=fine, clusters=clusters, nprobe=nprobe,
                              kmeans_iters=int(kmeans_iters), seed=int(seed))
    packed = _resolve_pack(fine, pack)
    s = _normalize_shards(shards)
    b = batch or _pick_batch(fine.m)
    key = (spec_h, backend, b, s, packed)

    def build():
        coarse = get_plan(module_for_spec(_coarse_spec(spec_h)),
                          backend="jnp", batch=b, pack=packed)
        prepare, chunk_fn, row_update = _build_hier_executable(
            spec_h, coarse, b, s, packed)
        return HierarchicalPlan(
            spec=spec_h, backend=backend, batch=b, shards=s, packed=packed,
            _prepare=prepare, _chunk_fn=chunk_fn, _row_update=row_update,
            stages=(coarse,))

    return _lookup_or_insert(key, build)
