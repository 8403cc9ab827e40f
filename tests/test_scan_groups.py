"""The flat plan's tournament scans its row tiles in blocks.

Each scan step of the jnp executable covers ``tiles_per_step``
consecutive row tiles (the execution block) and merges once, with no
gather.  The block size is read off the shape alone (``row_group``);
these tests shrink the step budget so that small galleries run several
blocks, and pin that every block size gives what one tile per step
gives and what the tiled oracle gives: bit-identical for the integer
metrics, to float tolerance for eucl.

The sharded leg runs in a child process under 8 forced host devices
(``python tests/test_scan_groups.py --child``).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArchSpec, clear_plan_cache, get_plan
from repro.core.engine import executables as ex
from repro.core.engine import extract_plan_spec
from repro.core.executor import execute_module
from repro.kernels import ref as kref

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_engine import _data, _sim_module  # noqa: E402
from test_packed import _ternary_data, _ternary_module  # noqa: E402

DEVICES = 8
BATCH = 16


def _grouped_plan(mod, group, rows, **kw):
    """A fresh plan whose step budget holds ``group`` row tiles at
    micro-batch ``BATCH`` (``row_group`` may spread the tiles over the
    steps more evenly, never more per step)."""
    saved = ex._STEP_ELEMS
    ex._STEP_ELEMS = group * BATCH * rows
    try:
        clear_plan_cache()
        return get_plan(mod, batch=BATCH, **kw)
    finally:
        ex._STEP_ELEMS = saved
        clear_plan_cache()


def _assert_same(got, want, exact, what):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]),
                                  err_msg=f"indices: {what}")
    if exact:
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]),
                                      err_msg=f"values: {what}")
    else:
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=1e-4, err_msg=f"values: {what}")


def _dims_per_tile(mod):
    return extract_plan_spec(mod).dims_per_tile


def _case_plain(metric, largest, n, k, rows, dim=100, pack=None,
                codes=None):
    """``codes``: the gallery repeats that many distinct rows, so most
    distances tie and ties go to the lower row."""
    def run(rng):
        m = 9
        arch = ArchSpec(rows=rows, cols=32)
        mod = _sim_module(metric, k, largest, m, n, dim, arch)
        q, p = _data(rng, metric, m, n, dim)
        if codes:
            p = p[rng.integers(0, codes, n)]
        oracle = execute_module(mod, q, p)
        if metric in ("hamming", "eucl"):
            tiled = kref.cam_topk_tiled(
                jnp.asarray(q), jnp.asarray(p), metric=metric, k=k,
                largest=largest, tile_rows=rows,
                dims_per_tile=_dims_per_tile(mod))
            _assert_same(oracle, tiled, metric == "hamming", "oracle")
        return [(g, _grouped_plan(mod, g, rows, pack=pack).execute(q, p))
                for g in (1, 2, 3, 5)], oracle, metric != "eucl"
    return run


def _case_ternary(pack):
    def run(rng):
        m, n, dim, k, rows = 9, 211, 77, 5, 16
        arch = ArchSpec(rows=rows, cols=32)
        mod = _ternary_module(m, n, dim, k, arch)
        q, p, care = _ternary_data(rng, m, n, dim)
        want = kref.cam_topk_tiled(
            jnp.asarray(q), jnp.asarray(p), metric="hamming", k=k,
            largest=False, tile_rows=rows,
            dims_per_tile=_dims_per_tile(mod), care=jnp.asarray(care))
        return [(g, _grouped_plan(mod, g, rows, pack=pack).execute(q, p, care))
                for g in (1, 2, 4)], want, True
    return run


def _case_update_rows(rng):
    """update_rows on a grouped plan: whole tiles are rewritten in the
    padded layout, and the result is the full re-prepare's."""
    m, n, dim, k, rows = 6, 205, 64, 4, 16         # 13 tiles, blocks of 5
    mod = _sim_module("hamming", k, False, m, n, dim, ArchSpec(rows=rows,
                                                              cols=32))
    q, p = _data(rng, "hamming", m, n, dim)
    idx = np.array([0, 17, 100, n - 1])
    new = _data(rng, "hamming", len(idx), n, dim)[0]
    p2 = p.copy()
    p2[idx] = new
    outs = []
    for g in (1, 5):
        saved = ex._STEP_ELEMS
        ex._STEP_ELEMS = g * BATCH * rows
        try:
            clear_plan_cache()
            plan = get_plan(mod, batch=BATCH)
            assert plan.tiles_per_step == g
            pj = jnp.asarray(p)
            plan.execute(q, pj)
            fb = plan.row_update_fallbacks
            pj2 = plan.update_rows(pj, idx, new)
            assert plan.row_update_fallbacks == fb
            outs.append((g, plan.execute(q, pj2)))
        finally:
            ex._STEP_ELEMS = saved
            clear_plan_cache()
    return outs, execute_module(mod, q, p2), True


def _case_sharded(rng):
    from repro.launch.mesh import forced_host_devices_env

    env = forced_host_devices_env(DEVICES)
    env.pop("REPRO_ENGINE_MAX_CHUNK", None)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0 and "GROUPED-SHARDED-OK" in out.stdout, (
        f"sharded child failed (rc={out.returncode}):\n"
        f"{out.stdout[-3000:]}\n{out.stderr[-3000:]}")
    return [], None, True


CASES = {
    # 13 row tiles: no block size divides them, the last block is padded
    "prime-tiles": _case_plain("hamming", False, 13 * 16 - 5, 6, 16,
                               pack=False),
    "prime-tiles-packed": _case_plain("hamming", False, 13 * 16 - 5, 6, 16,
                                      pack=True),
    # n < k with one tile, and with tiles narrower than k (each tile's
    # list padded with sentinels; later tiles' masked rows lose to them)
    "n-lt-k": _case_plain("hamming", False, 5, 6, 16),
    "n-lt-k-narrow-tiles": _case_plain("hamming", False, 7, 10, 4),
    # three distinct codes: ties everywhere, to the lower row
    "heavy-ties": _case_plain("hamming", False, 157, 7, 16, pack=False,
                              codes=3),
    "heavy-ties-packed": _case_plain("hamming", False, 157, 7, 16,
                                     pack=True, codes=3),
    "hamming-largest": _case_plain("hamming", True, 157, 6, 16),
    "dot-largest": _case_plain("dot", True, 157, 6, 16),
    "cos-largest": _case_plain("cos", True, 157, 6, 16),
    "eucl": _case_plain("eucl", False, 157, 6, 16),
    "ternary": _case_ternary(pack=False),
    "ternary-packed": _case_ternary(pack=True),
    "update-rows": _case_update_rows,
    "sharded": _case_sharded,
}


@pytest.mark.parametrize("case", list(CASES))
def test_grouped_scan_matches_tile_by_tile(case, rng):
    """Every block size gives the one-tile-per-step result and the
    oracle's: bit-identical for integer metrics, eucl to tolerance."""
    outs, want, exact = CASES[case](rng)
    for g, got in outs:
        _assert_same(got, outs[0][1], exact, f"{case}: {g} vs 1 tile/step")
        _assert_same(got, want, exact, f"{case}: {g} tiles/step vs oracle")


def _scan_lengths(jaxpr, depth=0, out=None):
    """``(depth, length)`` of every ``scan`` in a jaxpr, nested ones
    with their depth below the outermost."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        inner = depth
        if eqn.primitive.name == "scan":
            out.append((depth, eqn.params["length"]))
            inner = depth + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scan_lengths(sub, inner, out)
    return out


def test_sift_like_scan_runs_blocks_without_gather():
    """At a SIFT-like shape (128-wide float rows, 256 x 256 subarrays,
    micro-batch 128) the default budget puts 196 tiles in each of 4
    steps: the lowered program has no gather, its row scan runs
    ``scan_steps`` times, and ``plan.compile`` records the geometry."""
    from repro.obs import trace as obs

    n, dim, k = 200_000, 128, 10
    mod = _sim_module("eucl", k, False, 128, n, dim,
                      ArchSpec(rows=256, cols=256))
    clear_plan_cache()
    obs.stop()
    obs.tracer.clear()
    obs.enable()
    try:
        plan = get_plan(mod)
    finally:
        obs.stop()
    doc = obs.to_chrome()
    obs.tracer.clear()
    assert not plan.tiny and plan.batch == 128
    assert (plan.tiles_per_step, plan.scan_steps) == (196, 4)
    spans = [e for e in doc["traceEvents"]
             if e["name"] == "plan.compile" and e["ph"] == "B"]
    assert spans and spans[-1]["args"]["tiles_per_step"] == 196
    assert spans[-1]["args"]["scan_steps"] == 4

    stored = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    prepared = jax.eval_shape(plan._prepare, stored)
    assert prepared[0].shape[0] == 4 * 196           # padded to whole steps
    q = jax.ShapeDtypeStruct((plan.batch, dim), jnp.float32)
    lowered = plan._chunk_fn.lower(q, prepared)
    assert "gather" not in lowered.as_text()
    assert "gather(" not in lowered.compile().as_text()
    jaxpr = jax.make_jaxpr(plan._chunk_fn)(q, prepared).jaxpr
    rows_scans = [length for depth, length in _scan_lengths(jaxpr)
                  if depth == 0]
    assert rows_scans == [plan.scan_steps]
    clear_plan_cache()


def test_row_group_reads_the_shape():
    """``G`` spreads the tiles evenly over the fewest steps the budget
    allows, and one tile per step when a tile fills the budget."""
    budget = ex._STEP_ELEMS
    assert ex.row_group(256, 3907, 128) == 245       # SIFT1M: 16 steps
    assert ex.row_group(256, 3907, 1024) == 32
    assert ex.row_group(256, 10, 128) == 10          # capped at the grid
    assert ex.row_group(budget, 7, 1) == 1
    assert ex.row_group(budget * 2, 7, 1) == 1
    for tiles in (1, 2, 13, 63, 64, 65, 129, 3907):
        g = ex.row_group(256, tiles, 128)
        steps = -(-tiles // g)
        assert g * 256 * 128 <= max(budget, 256 * 128)
        assert steps * g - tiles < steps                # under a step padded


# ---------------------------------------------------------------------------
# child: the sharded executable under 8 forced host devices
# ---------------------------------------------------------------------------


def _child_main() -> int:
    assert jax.device_count() == DEVICES, jax.device_count()
    rng = np.random.default_rng(11)
    arch = ArchSpec(rows=16, cols=32)
    # 44 tiles -> 6 per shard, blocks of 3 (2 steps per shard); 23 rows
    # leave most shards padding only; 5 < k
    for metric, largest in (("hamming", False), ("dot", True),
                            ("eucl", False)):
        for n in (700, 23, 5):
            m, dim, k = 9, 100, 6
            mod = _sim_module(metric, k, largest, m, n, dim, arch)
            q, p = _data(rng, metric, m, n, dim)
            single = _grouped_plan(mod, 1, 16).execute(q, p)
            for g in (1, 3):
                plan = _grouped_plan(mod, g, 16, shards=DEVICES)
                assert plan.shards == DEVICES
                if n == 700:
                    assert plan.tiles_per_step == g
                _assert_same(plan.execute(q, p), single,
                             metric != "eucl", f"sharded {metric} n={n} g={g}")
    print("GROUPED-SHARDED-OK")
    return 0


if __name__ == "__main__" and "--child" in sys.argv:
    sys.exit(_child_main())
