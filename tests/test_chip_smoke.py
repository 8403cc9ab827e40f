"""``chip_smoke.py`` at a tiny size on the host.

The smoke itself refuses anything but a TPU; these tests drive its
phases directly, with Pallas in interpret mode, so its references,
checks and serving harness stay correct between chip runs.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from repro.core import clear_plan_cache

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_flat_codes_and_pallas_phases(smoke):
    rng = np.random.default_rng(0)
    flat = smoke.flat_data(rng, 3000, 24, n_queries=256)
    a = smoke.phase_flat(*flat)
    codes = smoke.code_data(rng, 3000, 256, n_queries=256)
    b = smoke.phase_codes(*codes, brute_queries=64)
    smoke.phase_pallas(flat, codes, a, b)
    clear_plan_cache()


def test_hdc_phase(smoke):
    assert smoke.phase_hdc(0, 512) in ("matmul", "pallas")
    clear_plan_cache()


def test_sharded_phase_refuses_a_clamped_plan(smoke):
    """One host device: a 4-shard request clamps to 1, which the
    four-chip phase must reject rather than report."""
    rng = np.random.default_rng(0)
    with pytest.raises(AssertionError, match="plan.shards=1"):
        smoke.phase_sharded(*smoke.flat_data(rng, 1000, 8, n_queries=64))
    clear_plan_cache()


def test_references_agree_with_dense_numpy(smoke):
    rng = np.random.default_rng(1)
    g, q = smoke.flat_data(rng, 900, 12, n_queries=32)
    d = ((q[:, None, :].astype(np.float64) - g[None]) ** 2).sum(-1)
    want = np.argsort(d, axis=1, kind="stable")[:, :smoke.K]
    assert smoke.recall_at_k(smoke.exact_l2_topk(q, g, smoke.K, block=256),
                             want) == 1.0
    codes, qc = smoke.code_data(rng, 900, 64, n_queries=16)
    h = (qc[:, None, :] != codes[None]).sum(-1)
    want = np.argsort(h, axis=1, kind="stable")[:, :smoke.K]
    np.testing.assert_array_equal(
        smoke.hamming_topk_numpy(qc, codes, smoke.K), want)
