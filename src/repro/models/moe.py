"""Mixture-of-experts FFN with capacity-based scatter/gather dispatch.

Design targets (DESIGN.md §6):

* **EP-shardable**: expert-stacked weights (E, d_in, d_out) shard the E axis
  over the ``model`` mesh axis; dispatch/combine become all-to-all-style
  collectives under pjit.
* **Compile-economical**: no (T, E, C) one-hot dispatch tensors; assignment
  uses a cumsum position + scatter-add, O(T*E) ints.
* **C4CAM integration**: the router is a ``matmul -> topk`` dataflow —
  exactly the paper's DotProdSimPattern.  With ``router_offload="cam"`` the
  top-k runs through the CAM search primitive (`repro.kernels`), i.e. the
  accelerator the paper compiles for; "dense" keeps plain jnp.  Both give
  identical routing decisions (ties break toward lower expert index in both
  paths).

Supports deepseek-moe (fine-grained: 64 routed top-6 + 2 always-on shared
experts) and phi3.5-moe (16 routed top-2).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import dense_init, pdtype

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    de = cfg.d_expert or cfg.d_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 7)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, pdtype(cfg)),
        "wi": jax.random.normal(ks[1], (e, d, de), pdtype(cfg)) * scale,
        "wg": jax.random.normal(ks[2], (e, d, de), pdtype(cfg)) * scale,
        "wo": jax.random.normal(ks[3], (e, de, d), pdtype(cfg)) / np.sqrt(de),
    }
    if cfg.n_shared_experts:
        ds = de * cfg.n_shared_experts
        p["shared_wi"] = dense_init(ks[4], d, ds, pdtype(cfg))
        p["shared_wg"] = dense_init(ks[5], d, ds, pdtype(cfg))
        p["shared_wo"] = dense_init(ks[6], ds, d, pdtype(cfg))
    return p


def router_topk(xt: jax.Array, router_w: jax.Array, k: int, offload: str
                ) -> Tuple[jax.Array, jax.Array]:
    """Select top-k experts: (T, D) tokens x (D, E) router -> (T,k) idx.

    ``offload="cam"`` treats the router rows as CAM-stored patterns and runs
    the C4CAM best-match primitive (dot metric, tiled subarray semantics) —
    the paper's DotProdSimPattern (matmul -> topk) executed on the CAM
    substrate.  ``offload="dense"`` is the plain jnp baseline.  Both use the
    same stable lower-index tie-breaking; scores are computed in fp32 in
    both paths (routing decisions agree up to fp32 summation order).
    """
    if offload == "cam":
        from ..kernels import ref as kref
        e, d = router_w.shape[1], router_w.shape[0]
        vals, idx = kref.cam_topk_tiled(
            xt.astype(jnp.float32), router_w.T.astype(jnp.float32),
            metric="dot", k=k, largest=True,
            tile_rows=min(32, e), dims_per_tile=min(128, d))
        return vals, idx
    scores = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def _moe_routed(router_w: jax.Array, wi: jax.Array, wg: jax.Array,
                wo: jax.Array, xt: jax.Array, cfg: ModelConfig, *,
                e_global: int, e_offset: int) -> jax.Array:
    """Routed-expert compute over a *local* expert slice ``[e_offset, +E_loc)``.

    Router scores/softmax/top-k span all ``e_global`` experts (router weights
    are replicated — deterministic across shards); dispatch and the expert
    FFNs touch only the local slice.  Used both by the single-device path
    (slice == all experts) and per-shard inside the EP ``shard_map`` (the
    cross-shard combine is a ``psum`` in the caller).
    """
    t, d = xt.shape
    e_loc = wi.shape[0]
    k = cfg.moe_top_k

    scores = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gate_all = jax.nn.softmax(scores, axis=-1)
    _, expert_idx = router_topk(xt, router_w, k, cfg.router_offload)
    expert_idx = jax.lax.stop_gradient(expert_idx)
    gates = jnp.take_along_axis(gate_all, expert_idx, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    capacity = int(np.ceil(t * k / e_global * cfg.capacity_factor))
    capacity = max(capacity, 8)

    # queue position of each (token, slot) within its *global* expert
    onehot = jax.nn.one_hot(expert_idx, e_global, dtype=jnp.int32)  # (T,k,E)
    flat = onehot.reshape(t * k, e_global)
    pos = jnp.cumsum(flat, axis=0) - 1                              # (T*k, E)
    pos = jnp.take_along_axis(pos, expert_idx.reshape(-1, 1), axis=1)[:, 0]

    eidx = expert_idx.reshape(-1)
    local = (eidx >= e_offset) & (eidx < e_offset + e_loc)
    keep = (pos < capacity) & local
    eloc_idx = jnp.where(local, eidx - e_offset, 0)
    safe_pos = jnp.where(keep, pos, capacity - 1)

    # dispatch: (E_loc, C, D) buffers
    buf = jnp.zeros((e_loc, capacity, d), xt.dtype)
    src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
    buf = buf.at[eloc_idx, safe_pos].add(src, mode="drop")

    hi = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xt.dtype))
    hg = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype))
    h = jax.nn.silu(hi) * hg
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(xt.dtype))

    # combine: gather back and weight (dropped / remote slots weight 0)
    gathered = out[eloc_idx, safe_pos]                              # (T*k, D)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(xt.dtype)
    return (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            rules=None) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).

    With ``rules`` (a :class:`~repro.models.sharding.ShardingRules` over a
    multi-device mesh) and ``E % model_size == 0``, the routed experts run
    expert-parallel under ``shard_map``: tokens stay replicated across the
    ``model`` axis (they are data-sharded only), every model shard computes
    the contribution of its local experts, and a ``psum`` over ``model``
    combines.  No all-to-all is needed because each shard already holds its
    data-shard's tokens — the EP collective cost is one (B,S,D) all-reduce.
    """
    b, s, d = x.shape
    xt_shape_back = (b, s, d)
    e = cfg.n_experts

    ep = (rules is not None and rules.model_axis is not None
          and rules.model_size() > 1 and e % rules.model_size() == 0)
    if ep:
        from jax.sharding import PartitionSpec as P
        mesh = rules.mesh
        maxis = rules.model_axis
        bd = rules.batch_axes
        n_shards = rules.model_size()
        e_loc = e // n_shards

        # combine with psum_scatter onto the sequence-parallel layout when
        # S divides the model axis: half the ring cost of a full psum AND
        # the result lands directly in the layer-boundary (S@model)
        # sharding (no re-shard before the residual add)
        scatter = s % n_shards == 0

        def body(xt_loc, router_w, wi, wg, wo):
            pos = jax.lax.axis_index(maxis)
            y = _moe_routed(router_w, wi, wg, wo,
                            xt_loc.reshape(-1, d), cfg,
                            e_global=e, e_offset=pos * e_loc)
            y = y.reshape(xt_loc.shape)
            if scatter:
                return jax.lax.psum_scatter(y, maxis, scatter_dimension=1,
                                            tiled=True)
            return jax.lax.psum(y, maxis)

        batch_spec = (bd if len(bd) > 1 else bd[0]) if bd else None
        bspec = P(batch_spec, None, None)
        out_spec = P(batch_spec, maxis, None) if scatter else bspec
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(bspec, P(None, None), P(maxis, None, None),
                      P(maxis, None, None), P(maxis, None, None)),
            out_specs=out_spec, check_vma=False,
        )(x, p["router"], p["wi"], p["wg"], p["wo"])
        yt = y.reshape(b * s, d)
        xt = x.reshape(b * s, d)
    else:
        xt = x.reshape(b * s, d)
        yt = _moe_routed(p["router"], p["wi"], p["wg"], p["wo"], xt, cfg,
                         e_global=e, e_offset=0)

    if cfg.n_shared_experts:
        hs = jax.nn.silu(xt @ p["shared_wi"].astype(x.dtype)) \
            * (xt @ p["shared_wg"].astype(x.dtype))
        yt = yt + hs @ p["shared_wo"].astype(x.dtype)
    return yt.reshape(xt_shape_back)


def aux_load_balance_loss(scores: jax.Array, expert_idx: jax.Array,
                          n_experts: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (optional in train loop)."""
    gate = jax.nn.softmax(scores.astype(jnp.float32), -1)
    me = gate.mean(0)
    ce = jnp.bincount(expert_idx.reshape(-1), length=n_experts) / expert_idx.size
    return n_experts * jnp.sum(me * ce)
