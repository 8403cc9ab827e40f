"""Fused Pallas kernel for HDC hypervector encoding.

Record-based encoding binds each feature's *key* (position) hypervector
with the hypervector of the feature's quantised *level*, then majority-
bundles across features::

    enc[m] = sign( sum_f  keys[f] * levels[q[m, f]] )        (bipolar)

The gather ``levels[q[m, f]]`` is hostile to the MXU, but the sum
decomposes over the (small, static) level alphabet into L matmuls::

    sum_f keys[f, h] * levels[q[m, f], h]
        = sum_l ( onehot_l @ keys )[m, h] * levels[l, h]

where ``onehot_l[m, f] = (q[m, f] == l)`` — a compare (VPU), a matmul
(MXU) and a broadcast multiply per level, no gathers.  Every product is
±1 and every sum is a small integer, so float32 accumulation is exact
and the kernel is **bit-identical** to :func:`repro.kernels.ref.
hdc_encode` (sign tie -> +1) regardless of accumulation order.

Grid = (M/bm, H/bh, F/bf); the F axis accumulates partial sums in a
VMEM scratch block, the last F step applies the sign.  ``levels`` is
blocked on H only (L is a handful of rows and rides along whole).
Padding contract: pad ``q`` with level 0 and ``keys`` with zero rows —
a padded feature's one-hot hits only zeroed key rows, contributing
nothing (the `ops.hdc_encode` wrapper does this).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cam_search import resolve_interpret


__all__ = ["hdc_encode_pallas"]


def _encode_kernel(q_ref, k_ref, l_ref, o_ref, acc_ref, *, n_levels: int,
                   nf: int):
    """One (i, h, f) grid step; f accumulates, last f extracts the sign."""
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                          # (bm, bf) int32 levels
    keys = k_ref[...].astype(jnp.float32)   # (bf, bh) bipolar (0 = pad)
    lv = l_ref[...].astype(jnp.float32)     # (L, bh) bipolar
    acc = acc_ref[...]
    for level in range(n_levels):
        onehot = (q == level).astype(jnp.float32)
        part = jax.lax.dot_general(
            onehot, keys, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + part * lv[level][None, :]
    acc_ref[...] = acc

    @pl.when(f == nf - 1)
    def _sign():
        o_ref[...] = jnp.where(acc_ref[...] >= 0, 1.0, -1.0)


def hdc_encode_pallas(level_idx: jax.Array, keys: jax.Array,
                      levels: jax.Array, *, block_m: int = 128,
                      block_f: int = 256, block_h: int = 256,
                      interpret: bool | None = None) -> jax.Array:
    """(M, H) bipolar encodings; operands must be block-aligned.

    ``level_idx`` (M, F) int32, ``keys`` (F, H) float32 bipolar (zero
    rows = padded features), ``levels`` (L, H) float32 bipolar with a
    small static L.  See `ops.hdc_encode` for the padding wrapper.
    """
    m, dim_f = level_idx.shape
    n_levels, h = levels.shape
    bm = min(block_m, max(8, m))
    bf = min(block_f, dim_f)
    bh = min(block_h, h)
    nm, nh, nf = -(-m // bm), -(-h // bh), -(-dim_f // bf)

    kern = functools.partial(_encode_kernel, n_levels=n_levels, nf=nf)
    out = pl.pallas_call(
        kern,
        grid=(nm, nh, nf),
        in_specs=[
            pl.BlockSpec((bm, bf), lambda i, hh, f: (i, f)),
            pl.BlockSpec((bf, bh), lambda i, hh, f: (f, hh)),
            pl.BlockSpec((n_levels, bh), lambda i, hh, f: (0, hh)),
        ],
        out_specs=pl.BlockSpec((bm, bh), lambda i, hh, f: (i, hh)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nh * bh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(level_idx, keys, levels)
    return out[:m, :h]
