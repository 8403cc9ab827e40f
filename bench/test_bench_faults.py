"""A whole run on the CPU at a small size, past the look for a chip,
with the timed path broken underneath: ``correct`` has to come out
false for each fault a serving cell can have, and true without one."""

import json
import math
import os

import numpy as np
import pytest

import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 4242
SMALL = {"n": 4096, "query_rows_traced": 16, "check_rows": 128}


def _alter_answer(server):
    """An answer altered where it is produced: the search executable
    returns the best index of every row shifted by one."""
    plan = server.plan
    inner = plan._chunk_fn

    def broken(q, prepared):
        v, i = inner(q, prepared)
        return v, i.at[:, 0].add(1)

    plan._chunk_fn = broken


def _drop_half(server):
    """Half of each micro-batch left out: its later rows get the
    answers of its earlier rows."""
    plan = server.plan
    inner = plan.finalize

    def broken(pending):
        v, i = (np.asarray(x) for x in inner(pending))
        m = v.shape[0]
        h = math.ceil(m / 2)
        v, i = v.copy(), i.copy()
        v[h:], i[h:] = v[:m - h], i[:m - h]
        return v, i

    plan.finalize = broken


FAULTS = {"none": None, "answer_altered": _alter_answer,
          "half_left_out": _drop_half}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    cfg = harness.load_cell(cell)[2]
    out = harness.run_cell(cell, SEED, 1.0, False, require_chip=False,
                           compile_cache=False, overrides=SMALL,
                           before_window=FAULTS[fault],
                           log=lambda s: None)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault == "none"), out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) >= set(cfg["limits"])
    assert set(out["metrics"]) == {m["name"] for m in
                                   harness.cell_metrics(BENCH, cell, False)}


def test_a_compile_inside_the_window_is_refused(monkeypatch, capsys):
    """A run in which a program compiled inside the measured window
    exits non-zero and prints no result."""
    import run

    def fake(*_a, **_kw):
        return {"correct": True, "run": {"compiles_in_window": 1}}

    monkeypatch.setattr(harness, "run_cell", fake)
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1"]) == 3
    assert capsys.readouterr().out == ""
