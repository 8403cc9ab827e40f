"""The leaf plan families: :class:`SearchPlan` and :class:`RangePlan`.

Thin subclasses of :class:`~.base.PlanBase` — each defines only its
family's structure: which module arguments are stored operands, the
shape of a chunk record, how chunks finalize into the module's output,
and the public ``update_rows`` signature.  Everything else (micro-batch
dispatch, pattern memoisation, fault hooks, the incremental-update
relay) is inherited.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...obs.trace import trace_span
from .base import PendingSearch, PlanBase, _size
from .executables import merge_shard_candidates

__all__ = ["SearchPlan", "RangePlan"]


@dataclass
class SearchPlan(PlanBase):
    """A compiled, reusable executable for one similarity-program shape.

    Chunks hold ``(values, indices, valid_rows)``; finalize runs the
    cross-shard candidate merge (sharded plans), slices ragged tails,
    and shapes ``(values, indices)`` for the compiled module.
    """

    family: str = field(default="search", repr=False)
    #: the row-tile tournament's shape (jnp backend; ``None`` for the
    #: fused Pallas kernel, which has no scan): row tiles per scan step
    #: and scan steps per micro-batch (per device when sharded)
    tiles_per_step: Optional[int] = None
    scan_steps: Optional[int] = None

    def _stored_sources(self, inputs) -> Tuple:
        spec = self.spec
        if spec.care_arg is None:
            return (inputs[spec.pattern_arg],)
        return (inputs[spec.pattern_arg], inputs[spec.care_arg])

    def _chunk_entry(self, out, valid: int):
        v, i = out
        return (v, i, valid)

    def finalize(self, pending: "PendingSearch"):
        """Materialise a dispatched search: cross-shard merge (sharded
        plans), ragged-tail slicing, chunk concatenation, output shaping."""
        with trace_span("plan.finalize"):
            return self._finalize(pending)

    def _finalize(self, pending: "PendingSearch"):
        spec = self.spec
        xp = np if self.shards > 1 else jnp
        vs, is_ = [], []
        for v, i, valid in pending.chunks:
            if self.shards > 1:
                v, i = merge_shard_candidates(v, i, k=spec.k,
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
        else:   # runtime M differs from the traced shape: mirror _as_2d
            v = v.reshape(lead + (k,))
            i = i.reshape(lead + (k,))
        return (v, i)

    # -- gallery mutation --------------------------------------------------

    def update_rows(self, gallery, indices, new_rows, care=None, *,
                    donate: bool = False):
        """Row-granular gallery mutation with incremental re-preparation.

        Returns the updated gallery as a fresh immutable ``jax.Array``
        whose prepared layout was derived from ``gallery``'s memoised
        layout by rewriting only the row tiles ``indices`` touch —
        encode/pack/layout runs on those tiles alone (sharded plans
        re-pin the leaves so each tile lands on its owning shard), so an
        online-learning workload touching 1% of a large gallery skips
        ~99% of the re-prepare work.  Results are bit-identical to a
        full re-prepare of the mutated gallery.

        ``care`` must be the plan's care mask for ternary programs (the
        memo keys on the (gallery, care) pair; the mask itself is
        immutable).  If ``gallery``'s layout is not memoised — numpy
        source, never dispatched, or evicted — the mutation still
        happens and the next dispatch re-prepares in full (counted in
        ``row_update_fallbacks``).

        ``donate=True`` reuses ``gallery``'s device buffer for the
        mutation (in-place scatter instead of a full-gallery copy —
        the copy otherwise dominates large-gallery updates).  Only pass
        it when nothing else will read ``gallery`` afterwards: the old
        array is invalidated, exactly like jit donation.
        """
        spec = self.spec
        if (care is None) != (spec.care_arg is None):
            raise ValueError("care mask must be passed iff the plan's "
                             "program is ternary")
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        self._validate_update(idx, new_rows)
        olds = (gallery,) if care is None else (gallery, care)
        # only the gallery rows mutate; a ternary care mask passes through
        upd = self._mutate_stored(olds, (new_rows,), idx, donate)
        return upd[0]


@dataclass
class RangePlan(PlanBase):
    """A compiled, reusable executable for one range-search program.

    Same plan-cache citizenship, micro-batching, pattern memoisation,
    packing and sharding as :class:`SearchPlan`; the result is a single
    ``(M, N)`` boolean match matrix instead of ``(values, indices)``.
    ``spec`` is a :class:`~.spec.RangeSpec`; chunks hold
    ``(match, valid_rows)``.
    """

    family: str = field(default="range", repr=False)

    def _stored_sources(self, inputs) -> Tuple:
        return tuple(inputs[i] for i in self.spec.pattern_args)

    def _chunk_entry(self, out, valid: int):
        return (out, valid)

    def finalize(self, pending: "PendingSearch"):
        """Materialise a dispatched range search into the boolean match
        matrix: concatenate per-shard slices (shard order == ascending
        global row order — no tournament), drop padded rows/chunks,
        shape for the compiled module."""
        with trace_span("plan.finalize"):
            return self._finalize(pending)

    def _finalize(self, pending: "PendingSearch"):
        spec = self.spec
        xp = np if self.shards > 1 else jnp
        outs = []
        for hit, valid in pending.chunks:
            if self.shards > 1:
                h = np.asarray(hit)                       # (S, B, cols)
                h = np.transpose(h, (1, 0, 2)).reshape(h.shape[1], -1)
            else:
                h = hit
            outs.append(h[:valid, :spec.n])
        if not outs:    # zero queries: well-shaped empty result
            outs = [xp.zeros((0, spec.n), bool)]
        match = outs[0] if len(outs) == 1 else xp.concatenate(outs, axis=0)
        m, lead = pending.m, pending.lead
        if m * spec.n == _size(spec.out_shape):
            return match.reshape(spec.out_shape)
        return match.reshape(lead + (spec.n,))

    def update_rows(self, stored, indices, new_rows, care=None, *,
                    donate: bool = False):
        """Row-granular mutation of a range plan's stored operands.

        ``stored`` is the current stored content — the pattern array
        for threshold mode, the ``(lo, hi)`` pair for interval mode —
        and ``new_rows`` matches that structure with ``(len(indices),
        dim)`` row blocks.  Returns the updated operand(s) in the same
        structure (jax arrays), memo-seeded incrementally exactly like
        :meth:`SearchPlan.update_rows` (including the ``donate``
        buffer-reuse contract).
        """
        if care is not None:
            raise ValueError("range plans have no care operand")
        spec = self.spec
        multi = len(spec.pattern_args) == 2
        olds = tuple(stored) if multi else (stored,)
        news = tuple(new_rows) if multi else (new_rows,)
        if len(olds) != len(spec.pattern_args) or len(news) != len(olds):
            raise ValueError(
                f"expected {len(spec.pattern_args)} stored operand(s) "
                f"and matching new-row block(s)")
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        self._validate_update(idx, *news)
        upd = self._mutate_stored(olds, news, idx, donate)
        return upd if multi else upd[0]
