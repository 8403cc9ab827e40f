"""A traced whole run on the CPU at a small size reads the metrics of
the program's own batch-pipeline spans and counters: in the steady
cell the delivery time, the in-flight depth and the collector's time,
in a bulk cell the wait for a completion slot."""

import math

import pytest

import harness

SEED = 2**31 + 4343
SMALL = {"n": 4096, "query_rows_traced": 16, "check_rows": 128}
READ = {"sift1m-l2.steady": ("batch.deliver_ms", "batcher.inflight_depth",
                             "host.gc_ms"),
        "sift1m-l2.bulk": ("batcher.slot_wait_ms",)}


@pytest.mark.parametrize("cell", sorted(READ))
def test_traced_run_reads_the_pipeline_metrics(cell):
    out = harness.run_cell(cell, SEED, 1.0, True, require_chip=False,
                           compile_cache=False, overrides=SMALL,
                           trace_s=0.5, log=lambda s: None)
    assert out["correct"], out["checks"]
    for name in READ[cell]:
        assert math.isfinite(out["metrics"][name]["value"]), name
