"""Strict ``REPRO_*`` environment parsing (``repro.core.envcfg``).

The contract pinned here: garbage in any recognised variable raises a
``ValueError`` that names the variable, the offending value, and what
would have been accepted — it never silently becomes a default (the
historical failure mode: ``REPRO_ENGINE_PACK=offf`` meant *on*).
"""

import json
import math
import os

import pytest

from repro.core.envcfg import (env_choice, env_flag, env_float, env_gate,
                               env_int, env_path)


class TestEnvFlag:
    def test_unset_means_default(self, monkeypatch):
        monkeypatch.delenv("X_FLAG", raising=False)
        assert env_flag("X_FLAG", True) is True
        assert env_flag("X_FLAG", False) is False

    @pytest.mark.parametrize("raw,want", [
        ("1", True), ("true", True), ("ON", True), ("yes", True),
        ("0", False), ("False", False), ("off", False), ("NO", False),
    ])
    def test_spellings(self, monkeypatch, raw, want):
        monkeypatch.setenv("X_FLAG", raw)
        assert env_flag("X_FLAG", not want) is want

    def test_auto_means_default(self, monkeypatch):
        monkeypatch.setenv("X_FLAG", "auto")
        assert env_flag("X_FLAG", True) is True
        assert env_flag("X_FLAG", False) is False

    def test_garbage_raises_naming_the_variable(self, monkeypatch):
        monkeypatch.setenv("X_FLAG", "offf")
        with pytest.raises(ValueError, match="X_FLAG.*offf"):
            env_flag("X_FLAG", True)

    def test_auto_rejected_when_disallowed(self, monkeypatch):
        monkeypatch.setenv("X_FLAG", "auto")
        with pytest.raises(ValueError, match="X_FLAG"):
            env_flag("X_FLAG", True, auto_means_default=False)


class TestEnvInt:
    def test_parse_and_bounds(self, monkeypatch):
        monkeypatch.setenv("X_INT", " 42 ")
        assert env_int("X_INT", 7) == 42
        monkeypatch.delenv("X_INT")
        assert env_int("X_INT", 7) == 7

    @pytest.mark.parametrize("raw", ["1k", "3.5", "", "NaN"])
    def test_garbage_raises(self, monkeypatch, raw):
        monkeypatch.setenv("X_INT", raw)
        with pytest.raises(ValueError, match="X_INT"):
            env_int("X_INT", 7)

    def test_min_max_enforced(self, monkeypatch):
        monkeypatch.setenv("X_INT", "0")
        with pytest.raises(ValueError, match="X_INT.*>= 1"):
            env_int("X_INT", 7, min_value=1)
        monkeypatch.setenv("X_INT", "9")
        with pytest.raises(ValueError, match="X_INT.*<= 8"):
            env_int("X_INT", 7, max_value=8)


class TestEnvFloat:
    def test_parse(self, monkeypatch):
        monkeypatch.setenv("X_F", "2.5")
        assert env_float("X_F", 1.0) == 2.5

    def test_nan_rejected(self, monkeypatch):
        monkeypatch.setenv("X_F", "nan")
        with pytest.raises(ValueError, match="X_F"):
            env_float("X_F", 1.0)

    def test_min_enforced(self, monkeypatch):
        monkeypatch.setenv("X_F", "-1")
        with pytest.raises(ValueError, match="X_F.*>= 0"):
            env_float("X_F", 1.0, min_value=0.0)


class TestEnvChoice:
    def test_choice(self, monkeypatch):
        monkeypatch.setenv("X_C", "Ref")
        assert env_choice("X_C", "auto", ("auto", "ref")) == "ref"
        monkeypatch.setenv("X_C", "nope")
        with pytest.raises(ValueError, match="X_C.*auto/ref"):
            env_choice("X_C", "auto", ("auto", "ref"))


class TestEnvPath:
    def test_unset_means_default(self, monkeypatch):
        monkeypatch.delenv("X_P", raising=False)
        assert env_path("X_P") is None
        assert env_path("X_P", "/tmp/d.json") == "/tmp/d.json"

    def test_value_passes_through(self, monkeypatch):
        monkeypatch.setenv("X_P", "/tmp/trace.json")
        assert env_path("X_P") == "/tmp/trace.json"

    @pytest.mark.parametrize("raw", ["", "   "])
    def test_blank_is_a_quoting_accident_not_a_path(self, monkeypatch,
                                                    raw):
        monkeypatch.setenv("X_P", raw)
        with pytest.raises(ValueError, match="X_P"):
            env_path("X_P")


class TestEnvGate:
    def test_auto_off_and_value(self, monkeypatch):
        monkeypatch.delenv("X_G", raising=False)
        assert env_gate("X_G", 3.0) == 3.0
        monkeypatch.setenv("X_G", "auto")
        assert env_gate("X_G", 3.0) == 3.0
        monkeypatch.setenv("X_G", "off")
        assert env_gate("X_G", 3.0) == 0.0
        monkeypatch.setenv("X_G", "1.5")
        assert env_gate("X_G", 3.0) == 1.5
        monkeypatch.setenv("X_G", "fast")
        with pytest.raises(ValueError, match="X_G"):
            env_gate("X_G", 3.0)
        assert not math.isnan(env_gate("X_G2", 2.0))


class TestEngineKnobsAreStrict:
    """The engine's own knobs go through the strict parsers."""

    def test_max_chunk_garbage_raises(self, monkeypatch):
        from repro.core.engine import _pick_batch
        monkeypatch.setenv("REPRO_ENGINE_MAX_CHUNK", "1k")
        with pytest.raises(ValueError, match="REPRO_ENGINE_MAX_CHUNK"):
            _pick_batch(64)

    def test_pack_typo_raises_not_silently_on(self, monkeypatch):
        from types import SimpleNamespace

        from repro.core.engine import _resolve_pack
        monkeypatch.setenv("REPRO_ENGINE_PACK", "offf")
        with pytest.raises(ValueError, match="REPRO_ENGINE_PACK"):
            _resolve_pack(SimpleNamespace(metric="hamming"), None)

    def test_update_flag_garbage_raises(self, monkeypatch):
        from repro.core.engine import _update_enabled
        monkeypatch.setenv("REPRO_ENGINE_UPDATE", "2")
        with pytest.raises(ValueError, match="REPRO_ENGINE_UPDATE"):
            _update_enabled()

    def test_pattern_slots_must_be_positive(self, monkeypatch):
        from repro.core.engine import SearchPlan
        monkeypatch.setenv("REPRO_ENGINE_PATTERN_SLOTS", "0")
        with pytest.raises(ValueError,
                           match="REPRO_ENGINE_PATTERN_SLOTS"):
            SearchPlan._pattern_cache_slots()

    def test_hdc_kernel_garbage_raises(self, monkeypatch):
        from repro.hdc.encoding import _kernel_choice
        monkeypatch.setenv("REPRO_HDC_KERNEL", "fastest")
        with pytest.raises(ValueError, match="REPRO_HDC_KERNEL"):
            _kernel_choice()

    def test_serve_deadline_garbage_fails_at_construction(
            self, monkeypatch, rng):
        from repro.core import ArchSpec, get_plan
        from repro.serving import CamSearchServer
        from test_engine import _data, _sim_module

        mod = _sim_module("dot", 2, True, 4, 16, 16,
                          ArchSpec(rows=8, cols=16))
        plan = get_plan(mod)
        _, p = _data(rng, "dot", 4, 16, 16)
        monkeypatch.setenv("REPRO_SERVE_DEADLINE_MS", "soon")
        with pytest.raises(ValueError, match="REPRO_SERVE_DEADLINE_MS"):
            CamSearchServer(plan, p)

    def test_tenant_knobs_garbage_fails_at_registration(self, monkeypatch):
        from repro.serving import CamServingGateway
        gw = CamServingGateway(maint_ms=0.0)
        monkeypatch.setenv("REPRO_TENANT_RATE", "plenty")
        with pytest.raises(ValueError, match="REPRO_TENANT_RATE"):
            gw.register_tenant("t", object(), object())
        monkeypatch.delenv("REPRO_TENANT_RATE")
        monkeypatch.setenv("REPRO_TENANT_QUEUE", "0")
        with pytest.raises(ValueError, match="REPRO_TENANT_QUEUE"):
            gw.register_tenant("t", object(), object())

    def test_replica_knobs_garbage_fails_at_registration(
            self, monkeypatch, rng):
        from repro.core import ArchSpec, get_plan
        from repro.serving import CamServingGateway
        from test_engine import _data, _sim_module

        mod = _sim_module("dot", 2, True, 4, 16, 16,
                          ArchSpec(rows=8, cols=16))
        plan = get_plan(mod)
        _, p = _data(rng, "dot", 4, 16, 16)
        gw = CamServingGateway(maint_ms=0.0)
        monkeypatch.setenv("REPRO_SERVE_REPLICAS", "many")
        with pytest.raises(ValueError, match="REPRO_SERVE_REPLICAS"):
            gw.register_tenant("t", plan, p)
        monkeypatch.delenv("REPRO_SERVE_REPLICAS")
        monkeypatch.setenv("REPRO_SERVE_UNHEALTHY_K", "0")
        with pytest.raises(ValueError, match="REPRO_SERVE_UNHEALTHY_K"):
            gw.register_tenant("t", plan, p)

    def test_gateway_maint_garbage_fails_at_construction(self, monkeypatch):
        from repro.serving import CamServingGateway
        monkeypatch.setenv("REPRO_SERVE_MAINT_MS", "often")
        with pytest.raises(ValueError, match="REPRO_SERVE_MAINT_MS"):
            CamServingGateway()

    def test_tiny_cells_garbage_raises(self, monkeypatch):
        from repro.core.engine.cache import _tiny_plan
        from test_plan_cache_keys import _sim_specs
        monkeypatch.setenv("REPRO_ENGINE_TINY_CELLS", "lots")
        with pytest.raises(ValueError, match="REPRO_ENGINE_TINY_CELLS"):
            _tiny_plan(_sim_specs()[0], "jnp", 1)

    def test_trace_knobs_garbage_raises(self, monkeypatch):
        from repro.obs import trace as obs
        monkeypatch.setenv("REPRO_TRACE_EVENTS", "lots")
        with pytest.raises(ValueError, match="REPRO_TRACE_EVENTS"):
            obs.configure_from_env()
        monkeypatch.setenv("REPRO_TRACE_EVENTS", "0")
        with pytest.raises(ValueError, match="REPRO_TRACE_EVENTS.*>= 1"):
            obs.configure_from_env()
        monkeypatch.delenv("REPRO_TRACE_EVENTS")
        # an empty REPRO_TRACE is a shell quoting accident, not "off"
        monkeypatch.setenv("REPRO_TRACE", "")
        with pytest.raises(ValueError, match="REPRO_TRACE"):
            obs.configure_from_env()

    def test_hier_nprobe_strict_and_applied(self, monkeypatch):
        from repro.core import ArchSpec, clear_plan_cache
        from repro.core.engine import get_hierarchical_plan
        from test_engine import _sim_module

        mod = _sim_module("hamming", 2, False, 4, 64, 16,
                          ArchSpec(rows=8, cols=16))
        monkeypatch.setenv("REPRO_HIER_NPROBE", "some")
        with pytest.raises(ValueError, match="REPRO_HIER_NPROBE"):
            get_hierarchical_plan(mod, clusters=8)
        monkeypatch.setenv("REPRO_HIER_NPROBE", "-1")
        with pytest.raises(ValueError, match="REPRO_HIER_NPROBE"):
            get_hierarchical_plan(mod, clusters=8)
        clear_plan_cache()
        monkeypatch.setenv("REPRO_HIER_NPROBE", "3")
        plan = get_hierarchical_plan(mod, clusters=8)
        assert plan.spec.nprobe == 3
        # an explicit nprobe argument beats the environment default
        plan = get_hierarchical_plan(mod, clusters=8, nprobe=5)
        assert plan.spec.nprobe == 5


class TestTuneKnobsAreStrict:
    """Autotuner + plan-store knobs parse strictly at the call site."""

    def _mod(self):
        from repro.core import ArchSpec
        from test_engine import _sim_module
        return _sim_module("hamming", 2, False, 4, 32, 16,
                           ArchSpec(rows=8, cols=16))

    def test_tune_trials_strict(self, monkeypatch):
        from repro.tune import tune_plan
        import numpy as np
        q = np.zeros((4, 16), np.float32)
        p = np.zeros((32, 16), np.float32)
        monkeypatch.setenv("REPRO_TUNE_TRIALS", "many")
        with pytest.raises(ValueError, match="REPRO_TUNE_TRIALS"):
            tune_plan(self._mod(), q, p)
        monkeypatch.setenv("REPRO_TUNE_TRIALS", "0")
        with pytest.raises(ValueError, match="REPRO_TUNE_TRIALS"):
            tune_plan(self._mod(), q, p)

    def test_tune_reps_and_budget_strict(self, monkeypatch):
        from repro.tune import tune_plan
        import numpy as np
        q = np.zeros((4, 16), np.float32)
        p = np.zeros((32, 16), np.float32)
        monkeypatch.setenv("REPRO_TUNE_REPS", "thrice")
        with pytest.raises(ValueError, match="REPRO_TUNE_REPS"):
            tune_plan(self._mod(), q, p)
        monkeypatch.delenv("REPRO_TUNE_REPS")
        for bad in ("forever", "nan", "-1"):
            monkeypatch.setenv("REPRO_TUNE_BUDGET_S", bad)
            with pytest.raises(ValueError, match="REPRO_TUNE_BUDGET_S"):
                tune_plan(self._mod(), q, p)

    def test_tune_serve_flag_strict(self, monkeypatch):
        from repro.core import get_plan
        from repro.serving.server import _resolve_plan
        plan = get_plan(self._mod())
        monkeypatch.setenv("REPRO_TUNE_SERVE", "maybe")
        with pytest.raises(ValueError, match="REPRO_TUNE_SERVE"):
            _resolve_plan(plan)

    def test_plan_store_blank_raises(self, monkeypatch):
        from repro.tune import active_store
        monkeypatch.setenv("REPRO_PLAN_STORE", "")
        with pytest.raises(ValueError, match="REPRO_PLAN_STORE"):
            active_store()


class TestBenchSmokeDirRouting:
    """``save_bench_json`` smoke routing (the PR-10 path-handling fix):
    ``*_smoke`` records never land at the repo root, an unset dir falls
    back under the system temp dir, a relative dir is anchored there
    too (not under whatever cwd the bench runs from), and a blank dir
    raises instead of writing into ``""``."""

    def _common(self, monkeypatch):
        import importlib
        import pathlib
        root = str(pathlib.Path(__file__).resolve().parent.parent)
        monkeypatch.syspath_prepend(root)
        return importlib.import_module("benchmarks.common")

    def test_unset_routes_under_tempdir(self, monkeypatch):
        import tempfile
        common = self._common(monkeypatch)
        monkeypatch.delenv("REPRO_BENCH_SMOKE_DIR", raising=False)
        path = common.save_bench_json("routing_smoke", {"ok": 1})
        try:
            assert path.startswith(tempfile.gettempdir())
            assert not os.path.exists(
                os.path.join(common.ROOT, "BENCH_routing_smoke.json"))
        finally:
            os.unlink(path)

    def test_explicit_absolute_dir_is_used(self, monkeypatch, tmp_path):
        common = self._common(monkeypatch)
        monkeypatch.setenv("REPRO_BENCH_SMOKE_DIR", str(tmp_path))
        path = common.save_bench_json("routing_smoke", {"ok": 2})
        assert path == str(tmp_path / "BENCH_routing_smoke.json")
        with open(path) as f:
            assert json.load(f) == {"ok": 2}

    def test_relative_dir_is_anchored_under_tempdir(self, monkeypatch):
        import tempfile
        common = self._common(monkeypatch)
        monkeypatch.setenv("REPRO_BENCH_SMOKE_DIR", "rel-smoke-dir")
        path = common.save_bench_json("routing_smoke", {"ok": 3})
        try:
            assert path == os.path.join(tempfile.gettempdir(),
                                        "rel-smoke-dir",
                                        "BENCH_routing_smoke.json")
            assert not os.path.exists(
                os.path.join(os.getcwd(), "rel-smoke-dir"))
        finally:
            os.unlink(path)

    def test_blank_dir_raises(self, monkeypatch):
        common = self._common(monkeypatch)
        monkeypatch.setenv("REPRO_BENCH_SMOKE_DIR", "  ")
        with pytest.raises(ValueError, match="REPRO_BENCH_SMOKE_DIR"):
            common.save_bench_json("routing_smoke", {"ok": 4})

    def test_non_smoke_records_still_land_at_root(self, monkeypatch):
        common = self._common(monkeypatch)
        # don't actually write BENCH_x.json at the real repo root
        monkeypatch.setattr(common, "ROOT", str(
            __import__("tempfile").mkdtemp()))
        path = common.save_bench_json("baseline_record", {"ok": 5})
        assert os.path.dirname(path) == common.ROOT


class TestBenchGatesUseEnvcfg:
    """Every benchmark acceptance gate parses through ``env_gate`` —
    ``auto``/``off``/float semantics with strict errors, no ad-hoc
    ``os.environ`` parsing left behind."""

    @pytest.mark.parametrize("var,loader,auto", [
        ("REPRO_FOREST_GATE", "benchmarks.bench_forest", 2.0),
        ("REPRO_PACKED_GATE", "benchmarks.bench_packed", 4.0),
        ("REPRO_HDC_GATE", "benchmarks.bench_hdc", 3.0),
        ("REPRO_MULTITENANT_GATE", "benchmarks.bench_multitenant", 2.0),
        ("REPRO_TRACE_GATE", "benchmarks.bench_trace", 1.0),
        ("REPRO_TUNE_GATE", "benchmarks.bench_tune", 1.2),
    ])
    def test_gate_semantics(self, monkeypatch, var, loader, auto):
        import importlib
        import pathlib
        import sys
        root = str(pathlib.Path(__file__).resolve().parent.parent)
        monkeypatch.syspath_prepend(root)
        bench = importlib.import_module(loader)
        monkeypatch.delenv(var, raising=False)
        assert bench._gate() == auto
        monkeypatch.setenv(var, "off")
        assert bench._gate() == 0.0
        monkeypatch.setenv(var, "1.25")
        assert bench._gate() == 1.25
        monkeypatch.setenv(var, "fast")
        with pytest.raises(ValueError, match=var):
            bench._gate()

    def test_hier_wide_gate_semantics(self, monkeypatch):
        import importlib
        import pathlib
        root = str(pathlib.Path(__file__).resolve().parent.parent)
        monkeypatch.syspath_prepend(root)
        bench = importlib.import_module("benchmarks.bench_hier")
        monkeypatch.delenv("REPRO_HIER_WIDE_GATE", raising=False)
        assert bench._wide_gate() == 1.0
        monkeypatch.setenv("REPRO_HIER_WIDE_GATE", "off")
        assert bench._wide_gate() == 0.0
        monkeypatch.setenv("REPRO_HIER_WIDE_GATE", "slow")
        with pytest.raises(ValueError, match="REPRO_HIER_WIDE_GATE"):
            bench._wide_gate()

    def test_tune_warm_gate_semantics(self, monkeypatch):
        import importlib
        import pathlib
        root = str(pathlib.Path(__file__).resolve().parent.parent)
        monkeypatch.syspath_prepend(root)
        bench = importlib.import_module("benchmarks.bench_tune")
        monkeypatch.delenv("REPRO_TUNE_WARM_GATE", raising=False)
        assert bench._warm_gate() == 3.0
        monkeypatch.setenv("REPRO_TUNE_WARM_GATE", "off")
        assert bench._warm_gate() == 0.0
        monkeypatch.setenv("REPRO_TUNE_WARM_GATE", "cold")
        with pytest.raises(ValueError, match="REPRO_TUNE_WARM_GATE"):
            bench._warm_gate()
