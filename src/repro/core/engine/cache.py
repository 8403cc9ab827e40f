"""The process-wide plan cache and its public entry point, ``get_plan``.

One cache for every plan family: keys are ``(spec, backend, batch,
shards, packed, unroll)`` where the spec is a frozen dataclass —
:class:`~.spec.SimilaritySpec`, :class:`~.spec.RangeSpec` or
:class:`~.composite.HierarchicalSpec` — so keys from different families
can never collide.  Recompiling the same program, or a different
program with identical structure (exactly what a DSE sweep over
optimization targets produces), returns the *same* plan object and
reuses its jitted executables instead of re-tracing.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax

from ...obs.trace import trace_span, tracer
from ..envcfg import env_int
from ..ir import Module
from .base import PlanBase, _pick_batch
from .executables import (_build_pallas_executable,
                          _build_range_pallas_executable,
                          _build_range_scan_executable,
                          _build_range_sharded_executable,
                          _build_scan_executable, _build_sharded_executable,
                          _build_tiny_executable,
                          _build_tiny_range_executable, _dense_spec,
                          tournament_geometry)
from .plans import RangePlan, SearchPlan
from .spec import (RangeSpec, _resolve_pack, extract_plan_spec,
                   extract_range_spec)

_PLAN_CACHE: "OrderedDict[Tuple, PlanBase]" = OrderedDict()
#: LRU bound — a DSE sweep over many distinct geometries must not pin
#: every plan (and its memoised galleries) forever
_MAX_PLANS = 64
_CACHE_LOCK = threading.Lock()
#: pattern_* entries retain the pattern-memo counters of plans evicted
#: from the LRU, keeping plan_cache_stats() monotonic across evictions
_STATS = {"hits": 0, "misses": 0,
          "pattern_hits": 0, "pattern_misses": 0, "pattern_evictions": 0}


def _retire_plan(plan: PlanBase) -> None:
    """Fold an evicted plan's pattern counters into the retained stats.

    A server (or any live reference) may still be driving the evicted
    plan, so the live counters are never zeroed — that would make the
    holder's ``counters()`` telemetry jump backwards mid-serve.
    Instead the delta above the plan's ``_retired_*`` bases is folded
    into ``_STATS`` and the bases advance, which makes retirement
    idempotent: retiring twice (evict, re-insert, evict again) folds
    each increment exactly once, and :func:`plan_cache_stats` counts a
    live plan net of its bases so a re-inserted retired plan is never
    double-counted.

    Caller holds ``_CACHE_LOCK``; lock order ``_CACHE_LOCK`` ->
    ``_pattern_lock`` is safe (no path acquires them in reverse).
    """
    with plan._pattern_lock:
        _STATS["pattern_hits"] += plan.pattern_hits - plan._retired_hits
        _STATS["pattern_misses"] += plan.pattern_misses - plan._retired_misses
        _STATS["pattern_evictions"] += \
            plan.pattern_evictions - plan._retired_evictions
        plan._retired_hits = plan.pattern_hits
        plan._retired_misses = plan.pattern_misses
        plan._retired_evictions = plan.pattern_evictions


def _normalize_shards(shards: Optional[int]) -> int:
    """Effective shard count: ``None``/<=1 means unsharded; requests are
    clamped to the host's device count (a plan asking for 8-way sharding
    on a 1-device host degrades to the single-device executable)."""
    if shards is None or shards <= 1:
        return 1
    return max(1, min(int(shards), jax.device_count()))


def _cache_lookup(key: Tuple) -> Optional[PlanBase]:
    with _CACHE_LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            _STATS["hits"] += 1
            _PLAN_CACHE.move_to_end(key)
            return plan
        _STATS["misses"] += 1
    return None


def _cache_insert(key: Tuple, plan: PlanBase) -> PlanBase:
    with _CACHE_LOCK:
        # lost-race double insert is harmless but keep one canonical plan
        plan = _PLAN_CACHE.setdefault(key, plan)
        _PLAN_CACHE.move_to_end(key)
        while len(_PLAN_CACHE) > _MAX_PLANS:
            _, evicted = _PLAN_CACHE.popitem(last=False)
            _retire_plan(evicted)
    return plan


def _lookup_or_insert(key: Tuple, build: Callable[[], PlanBase]) -> PlanBase:
    """Shared cache participation for plan factories outside this module
    (the composite/hierarchical family): counted lookup, build on miss,
    canonical insert with the same LRU/race semantics as ``get_plan``."""
    plan = _cache_lookup(key)
    if plan is not None:
        return plan
    with trace_span("plan.compile",
                    args=None if not tracer.enabled else
                    {"key": repr(key[1:])}):
        built = build()
    return _cache_insert(key, built)


def _tiny_plan(spec, backend: str, shards: int) -> bool:
    """Small-program fast path eligibility (ROADMAP item 5).

    A plan is *tiny* when its whole gallery collapses into one dense
    tile with identical semantics: a single column tile (full-width
    distances — dense and tiled arithmetic coincide), the jnp backend,
    no sharding, and a physical cell count small enough that per-tile
    ``lax.scan`` stepping would dominate the arithmetic.  The threshold
    is ``REPRO_ENGINE_TINY_CELLS`` (physical rows x logical dims;
    ``0`` disables the fast path).
    """
    if backend != "jnp" or shards != 1 or spec.grid_cols != 1:
        return False
    cells = spec.grid_rows * spec.tile_rows * spec.dim
    return cells <= env_int("REPRO_ENGINE_TINY_CELLS", 32768, min_value=0)


def get_plan(module: Module, *, backend: str = "jnp",
             batch: Optional[int] = None,
             shards: Optional[int] = None,
             pack: Optional[bool] = None,
             unroll: Optional[int] = None) -> Optional[PlanBase]:
    """Plan for a partitioned module, from the cache when possible.

    ``shards > 1`` selects the multi-device executable: gallery rows
    sharded over a ``("data",)`` mesh, cross-device ``merge_topk``
    tournament (see ``_build_sharded_executable``).  The effective shard
    count is part of the plan-cache key.

    ``pack`` selects bit-packed execution (uint32 lanes, XOR+popcount):
    ``None`` auto-packs binary/bipolar metrics (hamming / dot / cos) —
    bit-identical results at 1/32nd the gallery footprint — ``False``
    forces the float path, ``True`` on an analog metric raises.  The
    effective packing joins the plan-cache key: a packed and an unpacked
    plan for the same geometry are different executables and must never
    collide (their prepared operands have different dtypes).

    ``unroll`` sets the jnp ``lax.scan`` unroll factor (tile steps
    fused per scan iteration) — a pure scheduling knob with identical
    arithmetic at any value, exposed as an autotuner search axis.
    ``None`` means 1; the pallas backend has no scan to unroll and
    always normalises to 1.  The effective factor joins the cache key.

    When a persistent plan store is configured (``REPRO_PLAN_STORE``),
    a freshly built single-device jnp plan additionally consults it for
    an AOT-serialized executable pair matching this exact key — adopted
    executables skip XLA compilation entirely (see ``repro.tune``).

    Returns ``None`` when the module is not a pure similarity program
    (callers then fall back to the IR interpreter).
    """
    try:
        spec = extract_plan_spec(module)
        if spec is None:
            spec = extract_range_spec(module)
    except Exception:       # malformed/exotic IR: the interpreter handles it
        spec = None
    if spec is None:
        return None
    if backend not in ("jnp", "pallas"):
        return None
    if shards is not None and shards > 1 and backend != "jnp":
        # checked on the *requested* count, before device clamping, so
        # the refusal does not depend on how many devices this host has
        raise ValueError(
            f"sharded plans require the 'jnp' backend, got {backend!r}")
    is_range = isinstance(spec, RangeSpec)
    packed = _resolve_pack(spec, pack)
    if is_range and backend == "pallas" and packed:
        # the fused range kernels take float cells; the packed popcount
        # range path lives in the jnp executable
        if pack:
            raise ValueError(
                "packed range search requires the 'jnp' backend")
        packed = False
    if getattr(spec, "care_arg", None) is not None and not packed \
            and backend == "pallas":
        raise ValueError(
            "ternary (care-masked) search on the pallas backend requires "
            "packed execution; pass pack=True (and unset "
            "REPRO_ENGINE_PACK=off if the kill switch disabled auto-pack)")
    s = _normalize_shards(shards)
    b = batch or _pick_batch(spec.m)
    u = 1 if unroll is None or backend == "pallas" else max(1, int(unroll))
    key = (spec, backend, b, s, packed, u)
    plan = _cache_lookup(key)
    if plan is not None:
        return plan
    tiny = _tiny_plan(spec, backend, s)
    # the row-tile tournament's shape, which a search plan on the jnp
    # backend records: read off the spec, it is no part of the key
    geometry = {}
    if not is_range and backend == "jnp":
        group, steps = tournament_geometry(
            _dense_spec(spec) if tiny else spec, b, s)
        geometry = {"tiles_per_step": group, "scan_steps": steps}
    with trace_span("plan.compile",
                    args=None if not tracer.enabled else
                    {"family": "range" if is_range else "search",
                     "backend": backend, "batch": b, "shards": s,
                     "packed": packed, "unroll": u, **geometry}):
        plan = _build_leaf_plan(spec, backend, b, s, packed, tiny,
                                is_range, u)
        for name, value in geometry.items():
            setattr(plan, name, value)
        _maybe_adopt_stored_exec(plan)
    return _cache_insert(key, plan)


def _maybe_adopt_stored_exec(plan: PlanBase) -> None:
    """Swap a freshly built plan's jitted executables for AOT-serialized
    ones from the persistent plan store, when one is configured and
    holds a matching entry.

    Only single-device jnp non-tiny plans are eligible (tiny plans are
    shape-polymorphic, sharded plans bake in a device topology, pallas
    kernels carry their own compilation path).  The engine never
    imports ``repro.tune`` at module scope — the store stays an
    optional layer above the engine.
    """
    if plan.backend != "jnp" or plan.shards != 1 or plan.tiny:
        return
    try:
        from ...tune.store import active_store
        store = active_store()
    except Exception:       # tune layer unavailable: engine stays standalone
        return
    if store is not None:
        store.adopt_executables(plan)


def _build_leaf_plan(spec, backend: str, b: int, s: int, packed: bool,
                     tiny: bool, is_range: bool, unroll: int = 1) -> PlanBase:
    if is_range:
        if s > 1:
            prepare, chunk_fn, row_update = _build_range_sharded_executable(
                spec, b, s, packed=packed, unroll=unroll)
        elif backend == "pallas":
            prepare, chunk_fn, row_update = _build_range_pallas_executable(
                spec, b)
        elif tiny:
            prepare, chunk_fn, row_update = _build_tiny_range_executable(
                spec, b, packed=packed, unroll=unroll)
        else:
            prepare, chunk_fn, row_update = _build_range_scan_executable(
                spec, b, packed=packed, unroll=unroll)
        plan = RangePlan(spec=spec, backend=backend, batch=b, shards=s,
                         packed=packed, tiny=tiny, unroll=unroll,
                         _prepare=prepare,
                         _chunk_fn=chunk_fn, _row_update=row_update)
    else:
        if s > 1:
            prepare, chunk_fn, row_update = _build_sharded_executable(
                spec, b, s, packed=packed, unroll=unroll)
        elif backend == "pallas":
            prepare, chunk_fn, row_update = _build_pallas_executable(
                spec, b, packed=packed)
        elif tiny:
            prepare, chunk_fn, row_update = _build_tiny_executable(
                spec, b, packed=packed, unroll=unroll)
        else:
            prepare, chunk_fn, row_update = _build_scan_executable(
                spec, b, packed=packed, unroll=unroll)
        plan = SearchPlan(spec=spec, backend=backend, batch=b, shards=s,
                          packed=packed, tiny=tiny, unroll=unroll,
                          _prepare=prepare,
                          _chunk_fn=chunk_fn, _row_update=row_update)
    return plan


def plan_cache_stats() -> Dict[str, int]:
    """Process-wide cache counters.

    Plan cache (hits / misses / live plans) plus the pattern-prep memo
    counters (each plan's memoised prepared-gallery LRU — see
    ``PlanBase._prepared_patterns``): ``pattern_hits`` /
    ``pattern_misses`` / ``pattern_evictions``, summed over the live
    plans plus the retained totals of plans the 64-slot LRU evicted —
    monotonic until :func:`clear_plan_cache` resets everything.
    """
    # the whole aggregation holds _CACHE_LOCK so a concurrent eviction
    # cannot move a plan's counters into _STATS between the snapshot and
    # the live sum (which would transiently undercount); the established
    # lock order _CACHE_LOCK -> _pattern_lock makes the nesting safe
    with _CACHE_LOCK:
        out = {"hits": _STATS["hits"], "misses": _STATS["misses"],
               "plans": len(_PLAN_CACHE)}
        ph = _STATS["pattern_hits"]
        pm = _STATS["pattern_misses"]
        pe = _STATS["pattern_evictions"]
        for p in _PLAN_CACHE.values():
            with p._pattern_lock:
                # net of the retired bases: a previously-evicted plan
                # that found its way back into the cache already has
                # its pre-retirement counts folded into _STATS above
                ph += p.pattern_hits - p._retired_hits
                pm += p.pattern_misses - p._retired_misses
                pe += p.pattern_evictions - p._retired_evictions
    out.update(pattern_hits=ph, pattern_misses=pm, pattern_evictions=pe)
    return out


def clear_plan_cache() -> None:
    with _CACHE_LOCK:
        _PLAN_CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0
