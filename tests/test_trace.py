"""Execution tracing (``repro.obs``): recorder semantics, Chrome
export validity, and end-to-end followability.

The load-bearing pins:

* the disabled path allocates nothing — ``trace_span`` returns one
  shared singleton and ``trace_begin`` returns ``None``;
* the export is always Perfetto-loadable — every ``B`` has an ``E``
  (synthesised at the horizon for spans still open), orphan ``E``
  whose ``B`` was ring-evicted are dropped, timestamps are monotonic;
* the ring is bounded — capacity evicts oldest, never grows;
* one multi-tenant request is followable across the gateway, batcher
  and engine threads: the gateway ``gw.route`` instant links the
  gateway rid to the serving rid, and both request tracks plus the
  engine spans land in the same export.
"""

import json
import threading

import numpy as np
import pytest

from repro.core import ArchSpec, RangeSpec, SimilaritySpec, compile_fn
from repro.obs import trace as obs
from repro.serving import CamSearchServer, CamServingGateway

N, DIM, K = 96, 16, 3


def _knn(q, gallery):
    d = q.unsqueeze(1).sub(gallery).norm(p=2, dim=-1)
    return d.topk(K, largest=False)


@pytest.fixture(scope="module")
def compiled():
    rng = np.random.default_rng(5)
    gal = rng.standard_normal((N, DIM)).astype(np.float32)
    prog = compile_fn(_knn, [np.zeros((8, DIM), np.float32), gal],
                      ArchSpec(rows=32, cols=DIM))
    assert prog.engine_plan is not None
    return prog, gal


@pytest.fixture()
def clean_tracer():
    """Tracing off and empty before and after; capacity restored."""
    cap = obs.tracer.capacity
    obs.stop()
    obs.tracer.clear()
    yield obs.tracer
    obs.stop()
    obs.tracer.clear()
    obs.tracer.resize(cap)


def _events(doc, ph=None, pid=None, name=None):
    pids = {e["args"]["name"]: e["pid"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"}
    out = []
    for e in doc["traceEvents"]:
        if e["ph"] == "M":
            continue
        if ph is not None and e["ph"] != ph:
            continue
        if pid is not None and e["pid"] != pids.get(pid):
            continue
        if name is not None and e["name"] != name:
            continue
        out.append(e)
    return out


def _assert_valid_chrome(doc):
    """Every B has an E (per pid/tid, LIFO), timestamps monotonic."""
    json.dumps(doc)                         # serialisable
    assert doc["displayTimeUnit"] == "ms"
    stacks = {}
    last_ts = -1.0
    for e in doc["traceEvents"]:
        if e["ph"] == "M":
            continue
        assert e["ts"] >= 0
        if e["ph"] == "B":
            stacks.setdefault((e["pid"], e["tid"]), []).append(e)
        elif e["ph"] == "E":
            stack = stacks.get((e["pid"], e["tid"]))
            assert stack, f"E without open B: {e}"
            stack.pop()
        last_ts = max(last_ts, e["ts"])
    for key, stack in stacks.items():
        assert not stack, f"unterminated B on {key}: {stack}"


class TestDisabledPath:
    def test_span_is_shared_singleton(self, clean_tracer):
        s1 = obs.trace_span("a")
        s2 = obs.trace_span("b", "serving", args={"x": 1})
        assert s1 is s2                     # no allocation when off
        with s1:
            pass
        assert len(clean_tracer) == 0

    def test_begin_and_instant_are_noops(self, clean_tracer):
        assert obs.trace_begin("r") is None
        obs.instant("i", "gateway", {"reason": "x"})
        assert len(clean_tracer) == 0


class TestRecorder:
    def test_nesting_and_pairing(self, clean_tracer):
        obs.enable()
        with obs.trace_span("outer"):
            with obs.trace_span("inner"):
                pass
        obs.stop()
        doc = obs.to_chrome()
        _assert_valid_chrome(doc)
        names = [(e["name"], e["ph"]) for e in doc["traceEvents"]
                 if e["ph"] in "BE"]
        assert names == [("outer", "B"), ("inner", "B"),
                         ("inner", "E"), ("outer", "E")]

    def test_unterminated_b_closed_at_horizon(self, clean_tracer):
        obs.enable()
        clean_tracer.emit("B", "never_closed", "engine",
                          clean_tracer.now())
        with obs.trace_span("ok"):
            pass
        obs.stop()
        _assert_valid_chrome(obs.to_chrome())

    def test_orphan_e_from_eviction_dropped(self, clean_tracer):
        obs.enable(capacity=8)
        for _ in range(50):                 # Bs evicted, tail Es orphan
            with obs.trace_span("s"):
                pass
        obs.stop()
        assert len(clean_tracer) == 8       # bounded
        _assert_valid_chrome(obs.to_chrome())

    def test_capacity_grows_and_shrinks_preserving_events(
            self, clean_tracer):
        obs.enable(capacity=4)
        with obs.trace_span("keep"):
            pass
        obs.enable(capacity=16)
        assert len(clean_tracer) == 2
        assert clean_tracer.capacity == 16

    def test_cross_thread_handle_pins_origin_tid(self, clean_tracer):
        obs.enable()
        h = obs.trace_begin("request", "serving", {"rid": 1})
        origin = threading.get_ident()

        def worker():
            h.lap("request.queue_wait")
            h.end()

        t = threading.Thread(target=worker, name="completer")
        t.start()
        t.join()
        obs.stop()
        xs = _events(obs.to_chrome(), ph="X")
        assert len(xs) == 2
        assert all(e["tid"] == origin for e in xs)
        whole = next(e for e in xs if e["name"] == "request")
        assert whole["args"]["rid"] == 1
        assert whole["dur"] >= next(
            e for e in xs if e["name"] == "request.queue_wait")["dur"]

    def test_thread_and_process_names_exported(self, clean_tracer):
        obs.enable()

        def worker():
            with obs.trace_span("w", "serving"):
                pass

        t = threading.Thread(target=worker, name="batcher-0")
        t.start()
        t.join()
        obs.stop()
        doc = obs.to_chrome()
        procs = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        threads = {e["args"]["name"] for e in doc["traceEvents"]
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "serving" in procs
        assert "batcher-0" in threads

    def test_span_stats_aggregates(self, clean_tracer):
        obs.enable()
        for _ in range(3):
            with obs.trace_span("k"):
                pass
        h = obs.trace_begin("r", "serving")
        h.end()
        obs.stop()
        st = obs.span_stats()
        assert st["k"]["count"] == 3
        assert st["k"]["total_ms"] >= st["k"]["mean_ms"]
        assert "r" in st


class TestServedWorkloadTrace:
    def test_concurrent_serving_emits_followable_spans(
            self, compiled, clean_tracer, rng, tmp_path):
        """Batcher/completer spans nest correctly under concurrency and
        every request's queue-wait + service windows land on its own
        submitter thread track."""
        prog, gal = compiled
        obs.enable()
        with CamSearchServer(prog, gal, max_wait_ms=2.0) as srv:
            errs = []

            def client(c):
                try:
                    for _ in range(3):
                        q = rng.standard_normal((2, DIM)) \
                            .astype(np.float32)
                        srv.search(q, timeout=60)
                except Exception as e:      # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs[:1]
            path = srv.dump_trace(str(tmp_path / "serve.json"))
        obs.stop()
        with open(path) as f:
            doc = json.load(f)
        _assert_valid_chrome(doc)
        # per-batch pipeline spans from the serving threads
        # (batch.fill is a window handle -> X; the others nest -> B/E)
        assert _events(doc, ph="X", pid="serving", name="batch.fill")
        for span in ("batch.dispatch", "batch.finalize"):
            assert _events(doc, ph="B", pid="serving", name=span)
        # engine spans landed in the same export, on the engine pid
        assert _events(doc, ph="B", pid="engine", name="plan.dispatch")
        # every delivered request has its lifetime + both windows
        reqs = _events(doc, ph="X", pid="serving", name="request")
        waits = _events(doc, ph="X", pid="serving",
                        name="request.queue_wait")
        servs = _events(doc, ph="X", pid="serving",
                        name="request.service")
        assert len(reqs) == 12 and len(waits) == 12 and len(servs) == 12
        # request tracks are pinned to their submitter threads
        assert len({e["tid"] for e in reqs}) == 4
        for r in reqs:
            rid = r["args"]["rid"]
            w = [e for e in waits if e["tid"] == r["tid"]
                 and r["ts"] <= e["ts"] <= r["ts"] + r["dur"]]
            assert w, f"request {rid} has no queue-wait inside its span"

    def test_queue_wait_vs_service_split_in_snapshot(
            self, compiled, rng):
        prog, gal = compiled
        with CamSearchServer(prog, gal) as srv:
            q = rng.standard_normal((4, DIM)).astype(np.float32)
            for _ in range(3):
                srv.search(q, timeout=60)
            snap = srv.snapshot()
            health = srv.health()
        for key in ("queue_wait_p50_ms", "queue_wait_p95_ms",
                    "service_p50_ms", "service_p95_ms"):
            assert key in snap
            assert key in health["latency"]
        assert snap["service_p50_ms"] > 0
        # each component is pointwise <= the end-to-end latency, so its
        # p50 cannot exceed the blended p50
        assert snap["queue_wait_p50_ms"] <= snap["p50_ms"] + 1e-9
        assert snap["service_p50_ms"] <= snap["p50_ms"] + 1e-9


class TestGatewayFollowability:
    def test_multitenant_request_followable_across_components(
            self, compiled, clean_tracer, rng, tmp_path):
        """THE acceptance pin: a traced multi-tenant run produces a
        Perfetto-loadable export in which one request is followable
        gateway -> serving -> engine via the ``gw.route`` link."""
        prog, gal = compiled
        obs.enable()
        gw = CamServingGateway(maint_ms=0.0)
        try:
            gw.register_tenant("alpha", prog, gal)
            gw.register_tenant("beta", prog, gal)
            for tenant in ("alpha", "beta"):
                for _ in range(2):
                    q = rng.standard_normal((2, DIM)).astype(np.float32)
                    gw.search(tenant, q, timeout=60)
            path = gw.dump_trace(str(tmp_path / "gateway.json"))
        finally:
            gw.stop()
            obs.stop()
        with open(path) as f:
            doc = json.load(f)
        _assert_valid_chrome(doc)

        gw_reqs = _events(doc, ph="X", pid="gateway", name="request")
        routes = _events(doc, ph="i", pid="gateway", name="gw.route")
        srv_reqs = _events(doc, ph="X", pid="serving", name="request")
        assert len(gw_reqs) == 4 and len(routes) == 4
        assert {e["args"]["tenant"] for e in gw_reqs} == {"alpha", "beta"}
        for g in gw_reqs:
            # gateway request -> its route hop -> the serving request
            route = next(r for r in routes
                         if r["args"]["rid"] == g["args"]["rid"])
            server_rid = route["args"]["server_rid"]
            s = [e for e in srv_reqs
                 if e["args"]["rid"] == server_rid]
            assert len(s) == 1, \
                f"gateway rid {g['args']['rid']} not followable"
            # the admission window sits on the gateway track
        assert _events(doc, ph="X", pid="gateway", name="gw.admission")
        # and the engine's dispatch spans are in the same export
        assert _events(doc, ph="B", pid="engine", name="plan.dispatch")

    def test_reject_instants_carry_reason(self, compiled, clean_tracer):
        prog, gal = compiled
        obs.enable()
        gw = CamServingGateway(maint_ms=0.0)
        try:
            gw.register_tenant("limited", prog, gal,
                               rate=1.0, burst=2)
            q = np.zeros((2, DIM), np.float32)
            gw.search("limited", q, timeout=60)     # drains the burst
            with pytest.raises(Exception):
                gw.submit("limited", q)             # over rate
        finally:
            gw.stop()
            obs.stop()
        rejects = _events(obs.to_chrome(), ph="i", pid="gateway",
                          name="gw.reject")
        assert any(e["args"]["reason"] == "rate" for e in rejects)


class TestEnvDrivenTracing:
    def test_repro_trace_enables_and_sets_dump_path(
            self, clean_tracer, monkeypatch, tmp_path):
        p = str(tmp_path / "t.json")
        monkeypatch.setenv("REPRO_TRACE", p)
        monkeypatch.setenv("REPRO_TRACE_EVENTS", "128")
        assert obs.configure_from_env() == p
        assert obs.tracer.enabled
        assert obs.tracer.capacity == 128
        assert obs.tracer._atexit_path == p
        monkeypatch.delenv("REPRO_TRACE")
        assert obs.configure_from_env() is None
        assert obs.tracer._atexit_path is None


def _serve(prog, gal, rng, requests=8, **kw):
    """Submit ``requests`` two-row requests at once, wait for each and
    stop the server."""
    with CamSearchServer(prog, gal, max_wait_ms=1.0, **kw) as srv:
        reqs = [srv.submit(rng.standard_normal((2, DIM)).astype(np.float32))
                for _ in range(requests)]
        for r in reqs:
            r.wait(60)
    return srv, reqs


class TestBatchPipelineSpans:
    def test_served_batch_spans_carry_the_dispatch_batch_id(
            self, compiled, clean_tracer, rng):
        prog, gal = compiled
        obs.enable()
        _serve(prog, gal, rng)
        obs.stop()
        doc = obs.to_chrome()
        _assert_valid_chrome(doc)

        def ids(name):
            return sorted(e["args"]["batch"] for e in
                          _events(doc, ph="B", pid="serving", name=name))

        dispatched = ids("batch.dispatch")
        assert dispatched
        for name in ("batch.transfer", "batch.deliver", "batch.slot_wait"):
            assert ids(name) == dispatched, name
        delivered = _events(doc, ph="B", pid="serving", name="batch.deliver")
        assert sum(e["args"]["requests"] for e in delivered) == 8
        assert _events(doc, ph="B", pid="serving", name="batch.wait_request")

    def test_gc_collection_recorded_and_hook_removed_on_stop(
            self, clean_tracer):
        import gc

        before = list(gc.callbacks)
        obs.enable()
        gc.collect()
        obs.stop()
        assert gc.callbacks == before
        coll = _events(obs.to_chrome(), ph="X", pid="host", name="host.gc")
        full = [e for e in coll if e["args"]["generation"] == 2]
        assert full and full[-1]["args"]["collected"] >= 0
        assert full[-1]["dur"] > 0

    def test_tracing_off_builds_no_annotation_and_no_gc_hook(
            self, compiled, clean_tracer, rng, monkeypatch):
        import gc

        import jax.profiler

        def refuse(*_a, **_kw):
            raise AssertionError("TraceAnnotation built with tracing off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        before = list(gc.callbacks)
        prog, gal = compiled
        _serve(prog, gal, rng)
        gc.collect()
        assert gc.callbacks == before
        assert len(clean_tracer) == 0
        # the patch is live: the same span with tracing on builds one
        obs.enable()
        with pytest.raises(AssertionError, match="TraceAnnotation"):
            with obs.trace_span("plan.dispatch"):
                pass

    def test_profiler_capture_holds_program_spans(
            self, compiled, clean_tracer, rng, tmp_path):
        import glob

        import jax

        prog, gal = compiled
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        obs.enable()
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            _serve(prog, gal, rng)
        obs.stop()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        host = [p for p in pd.planes if p.name.startswith("/host:")]
        names = {e.name for p in host for ln in p.lines for e in ln.events}
        for span in ("batch.dispatch", "plan.dispatch", "batch.finalize",
                     "batch.deliver"):
            assert span in names, span


class TestInflightDepth:
    @pytest.mark.parametrize("failing", [False, True])
    def test_depth_bounded_and_gauge_back_to_zero_after_stop(
            self, compiled, rng, monkeypatch, failing):
        """``inflight_ahead / batches`` lies within ``[0, max_inflight +
        1]`` (the completion queue plus the batch in the completer's
        hands), and the gauge is 0 once the server has stopped, also
        when a batch failed after its dispatch."""
        prog, gal = compiled
        kw = {}
        if failing:
            plan = prog.engine_plan
            finalize, calls = plan.finalize, []

            def first_fails(pending):
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError("device lost")
                return finalize(pending)

            def no_fallback(level):
                if level != "primary":
                    raise RuntimeError("no fallback")

            monkeypatch.setattr(plan, "finalize", first_fails)
            kw["fault_injector"] = no_fallback
        srv, reqs = _serve(prog, gal, rng, requests=24, max_inflight=2,
                           max_batch=4, **kw)
        st = srv.stats
        assert st["inflight"] == 0
        assert st["batches"] >= 6
        assert 0 <= st["inflight_ahead"] <= 3 * st["batches"]
        failed = [r for r in reqs if r.result.error is not None]
        assert st["errors"] == len(failed)
        assert bool(failed) is failing


def _sim_spec(dim, n=33):
    return SimilaritySpec(
        metric="eucl", k=K, largest=False, tile_rows=16, dims_per_tile=32,
        grid_rows=-(-n // 16), grid_cols=-(-dim // 32), m=8, n=n, dim=dim,
        query_arg=0, pattern_arg=1, out_v_shape=(8, K), out_i_shape=(8, K),
        in_dtypes=("f32", "f32"))


def _range_spec(dim, n=33):
    return RangeSpec(
        mode="threshold", metric="hamming", threshold=1.5, below=True,
        tile_rows=16, dims_per_tile=32, grid_rows=-(-n // 16),
        grid_cols=-(-dim // 32), m=8, n=n, dim=dim, query_arg=0,
        pattern_args=(1,), out_shape=(8, n), in_dtypes=("f32", "f32"))


def _lowered_chunk(builder, spec, *extra):
    prepare, chunk_fn, _ = builder(spec, 8, *extra)
    prepared = prepare(np.ones((spec.n, spec.dim), np.float32))
    return chunk_fn.lower(np.ones((8, spec.dim), np.float32), prepared)


class TestExecutableNames:
    """The profiler names each XLA module after the function jitted:
    every chunk executable carries its own stable name, and the
    readers of device time find the search executable by its
    ``chunk_fn`` part."""

    @pytest.mark.parametrize("const, builder, spec, extra", [
        ("SEARCH_SCAN_CHUNK", "_build_scan_executable", _sim_spec(64), ()),
        ("SEARCH_TINY_CHUNK", "_build_tiny_executable", _sim_spec(32), ()),
        ("SEARCH_SHARDED_CHUNK", "_build_sharded_executable",
         _sim_spec(64), (1,)),
        ("SEARCH_PALLAS_CHUNK", "_build_pallas_executable",
         _sim_spec(64), ()),
        ("RANGE_SCAN_CHUNK", "_build_range_scan_executable",
         _range_spec(64), ()),
        ("RANGE_TINY_CHUNK", "_build_tiny_range_executable",
         _range_spec(32), ()),
        ("RANGE_SHARDED_CHUNK", "_build_range_sharded_executable",
         _range_spec(64), (1,)),
        ("RANGE_PALLAS_CHUNK", "_build_range_pallas_executable",
         _range_spec(64), ())])
    def test_chunk_module_is_named_by_its_constant(self, const, builder,
                                                   spec, extra):
        import re

        from repro.core.engine import executables as ex

        text = _lowered_chunk(getattr(ex, builder), spec, *extra).as_text()
        module = re.search(r"module @(\S+)", text).group(1)
        assert module == "jit_" + getattr(ex, const)
        assert "chunk_fn" in module

    def test_scan_phases_are_named_scopes(self):
        from repro.core.engine import executables as ex

        text = _lowered_chunk(ex._build_scan_executable,
                              _sim_spec(64)).as_text(debug_info=True)
        for scope in ("cam.distances", "cam.block_topk", "cam.merge_topk"):
            assert scope in text, scope
