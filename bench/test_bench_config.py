"""Configurations, traffic mixes and metric readers, found by name, and
the shape of BENCHMARK.json."""

import json
import os
import re

import numpy as np
import pytest

import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_its_config_and_traffic_by_name(cell):
    bench, entry, cfg, traffic = harness.load_cell(cell)
    assert entry["config"] == cfg["name"]
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "kinds",
                                       cfg["kind"] + ".py"))
    assert traffic["loop"] in ("open", "closed")
    assert int(traffic["rows"]) >= 1
    # every number the comparison prints has a limit, and every limit
    # that is set is a number
    kind = harness.load_module(
        os.path.join(harness.BENCH_DIR, "kinds", cfg["kind"] + ".py"),
        "kind_" + cfg["kind"])
    assert callable(kind.reference) and callable(kind.control)
    # the kind counts its own work and names its own controls
    assert set(kind.work(cfg, 128)) == {"ops", "bytes", "peak"}
    assert kind.CPU_CONTROL in kind.CONTROLS
    assert all(isinstance(v, (int, float)) for v in cfg["limits"].values())


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell.bulk")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_every_metric_a_cell_reports_has_a_reader(cell, trace):
    ms = harness.cell_metrics(BENCH, cell, trace)
    assert ms
    for m in ms:
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    if not trace:
        names = {m["name"] for m in ms}
        assert "setup_s" in names and len(names) >= 2


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    names += CELLS + [m["name"] for m in BENCH["end_to_end"]
                      + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(CELLS)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        reporting = [c for c in CELLS
                     if c in e2e[m["moves"]].get("workloads", CELLS)]
        assert set(m["workloads"]) <= set(reporting)
    for c in CELLS:
        assert harness.cell_metrics(BENCH, c, True)


def test_seed_streams_take_seeds_past_32_bits():
    a = harness.seed_streams(2**33 + 5)
    b = harness.seed_streams(2**33 + 5)
    assert np.array_equal(a["key"], b["key"])
    assert a["pool"].random() == b["pool"].random()
    c = harness.seed_streams(2**33 + 6)
    assert not np.array_equal(a["key"], c["key"])


def test_peaks_table_refuses_an_unknown_device():
    peaks = json.load(open(os.path.join(harness.BENCH_DIR, "peaks.json")))

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    class FakeJax:
        @staticmethod
        def devices():
            return [Dev()]

    with pytest.raises(harness.NoChip):
        harness.device_info(FakeJax, 1, peaks)
    Dev.device_kind = "TPU v5 lite"
    dev, pk = harness.device_info(FakeJax, 1, peaks)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip):
        harness.device_info(FakeJax, 4, peaks)
    Dev.platform = "cpu"
    with pytest.raises(harness.NoChip):
        harness.device_info(FakeJax, 1, peaks)
