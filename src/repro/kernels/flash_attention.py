"""Pallas TPU flash-attention forward kernel.

This is the TPU answer to the §Perf cell-B finding: the pure-JAX chunked
attention (`models/layers.attn_core`) still materializes (qc, T) score
blocks in HBM between fusions; here the whole online-softmax block loop
runs in VMEM and only the (bq, dh) output tile is written back.

Layout: heads are folded into the leading grid axis (GQA: q-head h reads
kv-head h // group).  Grid = (B*H, nq, nk) with the kv axis innermost and
sequential; scratch carries the running max ``m``, normalizer ``l`` and
the unnormalized accumulator across kv steps (the standard flash-forward
recurrence):

    m'   = max(m, rowmax(S))
    l'   = l * e^(m-m') + rowsum(e^(S-m'))
    acc' = acc * e^(m-m') + e^(S-m') @ V

Block shapes default to MXU-aligned (128 q rows x 128 kv rows x full
head dim); VMEM footprint = bq*dh + bk*dh * 2 + bq*bk + bq*(dh+2) floats
(~0.4 MB at dh=128), far inside the ~16 MB/core budget.  Causal masking
is done on global row/col indices so padding rows never contribute.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, prefix_len: int,
                  kv_len: Optional[int], bq: int, bk: int, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[...]                                   # (bq, dh)
    k = k_ref[...]                                   # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allow = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        allow = cols <= rows
        if prefix_len:
            allow = allow | (cols < prefix_len)
    if kv_len is not None:
        allow = allow & (cols < kv_len)
    s = jnp.where(allow, s, _NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (bq, bk)
    l_new = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_new = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, prefix_len: int = 0,
                           kv_len: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (B, S, H, dh); k/v: (B, T, KV, dh) -> (B, S, H, dh).

    GQA folds (B, head) into the grid's leading axis; kv blocks index the
    owning kv head.  ``kv_len`` masks cache tail rows (prefill/decode).
    """
    b, s, h, dh = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = 1.0 / (dh ** 0.5)

    bq = min(block_q, s)
    bk = min(block_k, t)
    nq = -(-s // bq)
    nk = -(-t // bk)
    pad_s, pad_t = nq * bq - s, nk * bk - t

    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kvh, t, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kvh, t, dh)
    if pad_s:
        qf = jnp.pad(qf, ((0, 0), (0, pad_s), (0, 0)))
    if pad_t:
        kf = jnp.pad(kf, ((0, 0), (0, pad_t), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_t), (0, 0)))
    # padded kv rows must never win: clamp the valid length
    eff_kv_len = t if kv_len is None else kv_len

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, prefix_len=prefix_len,
        kv_len=eff_kv_len, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, bk, dh), lambda bh, i, j, g=g: (bh // g, j, 0)),
            pl.BlockSpec((None, bk, dh), lambda bh, i, j, g=g: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * bq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :s].reshape(b, h, s, dh)
    return jnp.moveaxis(out, 1, 2)