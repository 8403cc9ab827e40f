"""The control of each configuration, at a size a test run holds: the
reference one precision step down, or with a stated guarantee broken,
put in the program's place, fails the configuration's limits; the
reference itself passes them."""

import json
import os

import numpy as np
import pytest

import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CONFIGS = [c["name"] for c in BENCH["configs"]]
SEED = 2**32 + 17


def _within(numbers: dict, limits: dict) -> bool:
    return all(v <= limits[k] for k, v in numbers.items())


@pytest.mark.parametrize("config", CONFIGS)
def test_control_fails_and_reference_passes(config):
    import jax

    entry = harness.find(BENCH["configs"], config, "config")
    cfg = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    cfg = {**cfg, "n": 8192}
    kind = harness.load_kind(cfg)
    streams = harness.seed_streams(SEED)
    gallery = kind.make_gallery(
        cfg, jax.random.wrap_key_data(np.asarray(streams["key"])))
    q = kind.make_queries(cfg, streams["pool"], 1, 512)[0]
    ref = kind.reference(cfg, gallery, q)
    assert _within(kind.compare(cfg, gallery, q, *ref, ref), cfg["limits"])
    # a CPU ignores a lower matmul precision: the kind names the control
    # that reads the same there (float: the bfloat16 passes written out)
    ctl = kind.control(cfg, gallery, q, **kind.CONTROLS[kind.CPU_CONTROL])
    assert not _within(kind.compare(cfg, gallery, q, *ctl, ref),
                       cfg["limits"])
