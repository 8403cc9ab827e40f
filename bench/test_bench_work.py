"""The work counts behind the roofline shares, against hand values."""

import importlib.util
import json
import os
import sys
from types import SimpleNamespace

import pytest

import harness
from work import least_time

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = json.load(open(os.path.join(HERE, "peaks.json")))["devices"][
    "TPU v5 lite"]


def _kind(name):
    return harness.load_kind({"kind": name})


def test_l2_counts_match_hand_values():
    cfg = {"n": 1_000_000, "dim": 128, "metric": "eucl"}
    w = _kind("l2_knn").work(cfg, 128)
    assert w["ops"] == 2 * 128 * 1_000_000 * 128 == 3.2768e10
    assert w["bytes"] == 1_000_000 * 128 * 4 == 5.12e8
    t, bound = least_time(w, V5E)
    assert bound == "hbm"
    assert abs(t - 5.12e8 / 819e9) < 1e-15          # 0.625 ms


def test_hamming_counts_match_hand_values():
    cfg = {"n": 1_000_000, "dim": 256, "metric": "hamming"}
    w = _kind("hamming_codes").work(cfg, 128)
    assert w["ops"] == 2 * 128 * 1_000_000 * 256 == 6.5536e10
    assert w["bytes"] == 1_000_000 * 256 / 8 == 3.2e7
    t, bound = least_time(w, V5E)
    assert bound == "compute"
    assert abs(t - 6.5536e10 / 393e12) < 1e-15      # 0.167 ms


def test_unknown_metric_has_no_count():
    """A kind counts the work of its own metric only."""
    with pytest.raises(ValueError):
        _kind("l2_knn").work({"n": 1, "dim": 1, "metric": "cos"}, 1)
    with pytest.raises(ValueError):
        _kind("hamming_codes").work({"n": 1, "dim": 1, "metric": "eucl"}, 1)


def test_roofline_reader_takes_rows_per_batch_and_trace_calls():
    """search_roofline: least time of a micro-batch of the window's rows
    per dispatched batch, over the traced device time per call."""
    sys.path.insert(0, os.path.join(HERE, "metrics"))
    spec = importlib.util.spec_from_file_location(
        "search_roofline", os.path.join(HERE, "metrics", "search_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    class Trace:
        @staticmethod
        def module_time(part):
            assert part == "chunk_fn"
            return 18, 18 * 0.166          # 18 traced calls of 166 ms

    ctx = SimpleNamespace(
        trace=Trace(), peaks=V5E, kind=_kind("hamming_codes"),
        cfg={"n": 1_000_000, "dim": 256, "metric": "hamming"},
        stats0={"batches": 10, "batched_rows": 1280},
        stats1={"batches": 126, "batched_rows": 1280 + 116 * 128})
    want = 100 * (2 * 128 * 1_000_000 * 256 / 393e12) / 0.166
    assert abs(reader.read(ctx) - want) < 1e-12
    ctx.trace = None
    assert reader.read(ctx) is None
