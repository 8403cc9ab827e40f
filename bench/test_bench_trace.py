"""The reduction from a profiler trace and host spans to device numbers."""

import gzip
import json
import os

import pytest

import trace_reduce as tr

#: a traced run's profile, recorded on a TPU v5e by
#: ``tools/record_trace.py --workload sift1m-l2.bulk --seed 4000000002
#: --seconds 3 --trace-s 0.12 --set n=8192`` (72 ms traced once the
#: profiler had started), beside what its reduction read and gave
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "l2_bulk_trace")


def _doc():
    """Two devices; one window [1000, 2000) ns of the trace clock."""
    ops0 = [["fusion.1", 900, 200],       # 1000..1100 inside
            ["fusion.2", 1050, 100],      # overlaps the first
            ["copy.3", 1400, 100],
            ["fusion.1", 1900, 300]]      # 1900..2000 inside
    ops1 = [["fusion.1", 1000, 500]]
    mods0 = [["jit_chunk_fn(17)", 900, 300], ["jit_chunk_fn(17)", 1850, 400],
             ["jit_other(3)", 1400, 100]]
    mods1 = [["jit_chunk_fn(17)", 1000, 500]]
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[tr.SYNC, 500, 10]]}]}
    return {"planes": [
        host,
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": ops0},
            {"name": tr.MODULES_LINE, "events": mods0}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": tr.OPS_LINE, "events": ops1},
            {"name": tr.MODULES_LINE, "events": mods1}]}]}


def test_union_merges_overlaps_and_clips():
    busy, gaps = tr.union([(0, 5), (3, 8), (10, 12), (20, 30)], 2, 25)
    assert busy == (8 - 2) + (12 - 10) + (25 - 20)
    assert gaps == [(8, 10), (12, 20)]
    assert tr.union([], 0, 4) == (0.0, [(0, 4)])


def test_reduce_busy_modules_ops_and_gaps():
    t = tr.reduce(_doc(), 1000, 2000, lambda s, e: f"gap{s}")
    assert t.devices == 2
    assert t.window_s == pytest.approx(1e-6)
    # device 0 busy 1000..1150, 1400..1500, 1900..2000 = 350 ns; device 1
    # 500 ns; averaged over the two
    assert t.busy_s == pytest.approx((350 + 500) / 2 / 1e9)
    # calls counted whole, by their middle: 900..1200 is in, 1850..2250
    # is out
    assert t.module_time("chunk_fn") == (2, pytest.approx(8e-7))
    assert t.module_time("other") == (1, pytest.approx(1e-7))
    assert t.ops["fusion.1"] == pytest.approx((100 + 100 + 500) / 1e9)
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    # longest gaps first: device 1's 1500..2000, then device 0's 1500..1900
    assert b["idle_gaps"][:2] == [["gap1500", pytest.approx(5e-7)],
                                  ["gap1500", pytest.approx(4e-7)]]


def test_reduce_without_a_device_finds_nothing():
    doc = {"planes": [_doc()["planes"][0]]}
    assert tr.reduce(doc, 0, 10) is None
    assert tr.sync_ns(_doc()) == 500


def test_host_spans_and_gap_labels():
    # chrome export: microseconds from its first event; the sync instant
    # anchors it to perf_counter
    chrome = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0},
        {"name": tr.SYNC, "ph": "i", "pid": 2, "tid": 9, "ts": 0.0},
        {"name": "batch.finalize", "ph": "B", "pid": 2, "tid": 5, "ts": 10.0},
        {"name": "plan.finalize", "ph": "B", "pid": 1, "tid": 5, "ts": 12.0},
        {"name": "plan.finalize", "ph": "E", "pid": 1, "tid": 5, "ts": 20.0},
        {"name": "batch.finalize", "ph": "E", "pid": 2, "tid": 5, "ts": 30.0},
        {"name": "request", "ph": "X", "pid": 2, "tid": 7, "ts": 5.0,
         "dur": 100.0},
        {"name": "plan.dispatch", "ph": "B", "pid": 1, "tid": 6, "ts": 40.0},
        {"name": "plan.dispatch", "ph": "E", "pid": 1, "tid": 6, "ts": 41.0},
    ]}
    sync = 1_000_000_000                         # perf_counter ns
    stats, ivs = tr.host_spans(chrome, sync, 1.0, 1.0 + 35e-6)
    assert set(stats) == {"batch.finalize", "plan.finalize", "request"}
    assert stats["plan.finalize"]["mean_ms"] == pytest.approx(8e-3)
    label = tr.gap_labeller(ivs, offset_ns=sync - 0)
    # a gap around 15 us after the sync: plan.finalize is innermost
    assert label(14_000, 16_000) == "plan.finalize"
    assert label(24_000, 26_000) == "batch.finalize"
    assert label(60_000, 70_000) == "no host span"


def test_recorded_trace_reduces_to_what_the_run_printed(tmp_path):
    """The profile a chip run wrote loads with the names the readers
    depend on (a device plane with ``XLA Ops`` and ``XLA Modules``, the
    search executable as ``jit_chunk_fn(<id>)``, the ``bench.sync``
    annotation on the host), and reduces on the CPU to what the run
    itself printed."""
    rec = json.load(open(os.path.join(RECORDED, "trace.json")))
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(RECORDED, "trace.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    doc = tr.load_xplane(str(path))
    devices = [p for p in doc["planes"] if p["name"].startswith("/device:")]
    lines = {ln["name"]: ln["events"] for ln in devices[0]["lines"]}
    assert {tr.OPS_LINE, tr.MODULES_LINE} <= set(lines)
    assert any(tr._module_name(e[0]) == "jit_chunk_fn"
               for e in lines[tr.MODULES_LINE])
    assert tr.sync_ns(doc) is not None

    t = tr.reduce_xplane(str(path), sync=rec["sync"], t0=rec["t0"],
                         t1=rec["t1"], intervals=rec["intervals"])
    want = rec["reduced"]
    assert t.window_s == want["window_s"]
    assert t.busy_s == want["busy_s"]
    assert t.devices == want["devices"] == 1
    assert {k: list(v) for k, v in t.modules.items()} == want["modules"]
    assert json.loads(json.dumps(t.breakdown())) == want["breakdown"]
    calls, secs = t.module_time("chunk_fn")
    assert calls > 0 and 0 < secs
    assert 0 < t.busy_s <= t.window_s
