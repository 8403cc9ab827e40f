"""Pallas CAM-search kernels vs the pure-jnp oracle.

Sweeps shapes / dtypes / metrics / k and asserts bit-exact indices and
allclose values (interpret=True executes the kernel body on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st  # skips cleanly without hypothesis

from repro.kernels import ops, ref


def _data(rng, metric, m, n, d, dtype=np.float32):
    if metric == "hamming":
        q = (rng.random((m, d)) > 0.5).astype(dtype)
        p = (rng.random((n, d)) > 0.5).astype(dtype)
    else:
        q = rng.standard_normal((m, d)).astype(dtype)
        p = rng.standard_normal((n, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(p)


SHAPES = [(1, 8, 16, 1), (10, 100, 64, 5), (7, 33, 130, 3),
          (128, 512, 256, 8), (3, 1000, 48, 10), (65, 129, 257, 4)]


@pytest.mark.parametrize("metric", ["hamming", "dot", "eucl"])
@pytest.mark.parametrize("m,n,d,k", SHAPES)
def test_pallas_topk_matches_oracle(metric, m, n, d, k, rng):
    q, p = _data(rng, metric, m, n, d)
    largest = metric == "dot"
    v1, i1 = ops.cam_topk(q, p, metric=metric, k=k, largest=largest,
                          tile_rows=32, dims_per_tile=64)
    v2, i2 = ref.cam_topk(q, p, metric=metric, k=k, largest=largest)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int8])
def test_pallas_topk_dtypes(dtype, rng):
    q = (rng.random((6, 96)) > 0.5).astype(dtype)
    p = (rng.random((50, 96)) > 0.5).astype(dtype)
    v1, i1 = ops.cam_topk(jnp.asarray(q), jnp.asarray(p), metric="hamming",
                          k=3, largest=False)
    v2, i2 = ref.cam_topk(jnp.asarray(q, jnp.float32),
                          jnp.asarray(p, jnp.float32),
                          metric="hamming", k=3, largest=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("block", [(8, 16), (16, 128), (128, 512)])
def test_pallas_block_shape_invariance(block, rng):
    """Different CAM subarray geometries must give identical results."""
    q, p = _data(rng, "eucl", 9, 77, 120)
    tr, dpt = block
    v1, i1 = ops.cam_topk(q, p, metric="eucl", k=5, largest=False,
                          tile_rows=tr, dims_per_tile=dpt)
    v2, i2 = ref.cam_topk(q, p, metric="eucl", k=5, largest=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-3)


def test_exact_and_range_match(rng):
    p = (rng.random((40, 64)) > 0.5).astype(np.float32)
    q = p[[3, 17, 25]].copy()
    q[2, :5] = 1 - q[2, :5]            # 5 mismatches in the third query
    ex = np.asarray(ops.cam_exact(jnp.asarray(q), jnp.asarray(p)))
    assert ex[0, 3] and ex[1, 17] and not ex[2].any()
    rg = np.asarray(ops.cam_range(jnp.asarray(q), jnp.asarray(p), 5.0))
    assert rg[2, 25]
    ex_ref = np.asarray(ref.cam_exact(jnp.asarray(q), jnp.asarray(p)))
    np.testing.assert_array_equal(ex, ex_ref)


@given(m=st.integers(1, 17), n=st.integers(1, 80), d=st.integers(1, 100),
       k=st.integers(1, 12), metric=st.sampled_from(["hamming", "dot", "eucl"]))
@settings(max_examples=25, deadline=None)
def test_tiled_reference_equals_dense(m, n, d, k, metric):
    """Property: the partitioned execution semantics == whole-array search."""
    rng = np.random.default_rng(m * 1000 + n * 10 + d)
    q, p = _data(rng, metric, m, n, d)
    largest = metric == "dot"
    v1, i1 = ref.cam_topk_tiled(q, p, metric=metric, k=k, largest=largest,
                                tile_rows=16, dims_per_tile=32)
    kk = min(k, n)
    v2, i2 = ref.cam_topk(q, p, metric=metric, k=kk, largest=largest)
    np.testing.assert_array_equal(np.asarray(i1)[:, :kk], np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1)[:, :kk], np.asarray(v2),
                               atol=1e-3)


def test_kernel_pad_sentinels_match_shared_helper(rng):
    """k > N padding: the Pallas wrapper must emit exactly the
    ``ref.pad_candidates`` sentinels (losing value, index 2**30) instead
    of hand-rolled constants, so kernel, engine, and tiled reference all
    agree bit-for-bit on the losing slots."""
    n, k = 5, 9
    for metric, largest in (("hamming", False), ("dot", True),
                            ("eucl", False)):
        q, p = _data(rng, metric, 4, n, 32)
        kv, ki = ops.cam_topk(q, p, metric=metric, k=k, largest=largest)
        # valid slots match the dense oracle at k' = n
        rv, ri = ref.cam_topk(q, p, metric=metric, k=n, largest=largest)
        np.testing.assert_array_equal(np.asarray(ki)[:, :n], np.asarray(ri))
        np.testing.assert_allclose(np.asarray(kv)[:, :n], np.asarray(rv),
                                   atol=1e-4)
        # losing slots are exactly pad_candidates' sentinels
        ev, ei = ref.pad_candidates(rv, ri, k, largest)
        np.testing.assert_array_equal(np.asarray(ki)[:, n:],
                                      np.asarray(ei)[:, n:])
        np.testing.assert_array_equal(np.asarray(kv)[:, n:],
                                      np.asarray(ev)[:, n:])
        lose = -np.inf if largest else np.inf
        assert np.all(np.asarray(kv)[:, n:] == lose)
        assert np.all(np.asarray(ki)[:, n:] == 2 ** 30)


def test_merge_topk_tie_break_lower_index():
    va = jnp.asarray([[1.0, 1.0]])
    ia = jnp.asarray([[4, 9]], dtype=jnp.int32)
    vb = jnp.asarray([[1.0, 0.5]])
    ib = jnp.asarray([[2, 3]], dtype=jnp.int32)
    v, i = ref.merge_topk(va, ia, vb, ib, k=2, largest=True)
    # stability: candidates listed first (a then b) win ties
    assert list(np.asarray(i)[0]) == [4, 9]


def test_distance_pallas_matches(rng):
    q, p = _data(rng, "eucl", 12, 56, 72)
    d1 = np.asarray(ops.cam_distances(q, p, metric="eucl"))
    d2 = np.asarray(ref.distances(q, p, "eucl"))
    np.testing.assert_allclose(d1, d2, atol=1e-3)


# The fused kernels keep each row block's running top-k in a lane-dense
# (rows, 128 * ceil(k / 128)) output slab.  These shapes put k inside
# and past one slab, and run every query through a single row block
# (m <= block_m) as well as several.
SLAB_SHAPES = [(1, 300, 40, 7), (128, 700, 96, 10), (5, 300, 40, 130),
               (130, 1000, 64, 129)]


@pytest.mark.parametrize("metric", ["hamming", "dot", "eucl"])
@pytest.mark.parametrize("m,n,d,k", SLAB_SHAPES)
def test_pallas_topk_lane_slab_matches_oracle(metric, m, n, d, k, rng):
    q, p = _data(rng, metric, m, n, d)
    largest = metric == "dot"
    v1, i1 = ops.cam_topk(q, p, metric=metric, k=k, largest=largest,
                          tile_rows=128, dims_per_tile=128)
    v2, i2 = ref.cam_topk(q, p, metric=metric, k=k, largest=largest)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-3)


@pytest.mark.parametrize("m,n,d,k", SLAB_SHAPES)
def test_pallas_packed_topk_lane_slab_matches_float_path(m, n, d, k, rng):
    from repro.kernels import packing as kpack

    q, p = _data(rng, "hamming", m, n, d)
    fv, fi = ops.cam_topk(q, p, metric="hamming", k=k, largest=False,
                          tile_rows=128, dims_per_tile=128)
    pv, pi = ops.cam_topk_packed(kpack.pack_bits(q), kpack.pack_bits(p),
                                 k=k, largest=False, tile_rows=128,
                                 lanes_per_tile=128)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(pi))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(pv))


def test_pallas_topk_ties_resolve_to_lower_index():
    """Duplicate pattern rows in different blocks: the running fold
    must keep the lower global index first, as a stable merge does."""
    p = np.zeros((600, 16), np.float32)
    p[[5, 130, 260, 599]] = 1.0               # four exact ties, 3 blocks
    q = np.ones((3, 16), np.float32)
    v, i = ops.cam_topk(jnp.asarray(q), jnp.asarray(p), metric="eucl",
                        k=6, largest=False, tile_rows=128,
                        dims_per_tile=128)
    assert np.asarray(i)[:, :4].tolist() == [[5, 130, 260, 599]] * 3
    assert np.asarray(i)[:, 4:].tolist() == [[0, 1]] * 3
