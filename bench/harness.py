"""One run of one benchmark cell.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and
a traffic mix.  Everything that belongs to one of them is found by
name:

* ``bench/configs/<config>.json``: the deployment's sizes and limits;
  its ``kind`` names ``bench/kinds/<kind>.py``, which builds the
  program through its public entry point, makes the data from the seed
  and holds the plain reference, the control and the comparison;
* ``bench/traffic/<mix>.json``: parameters for ``loadgen``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``
  returning a number or ``None`` when it finds nothing to read.

A run builds the server (set-up), measures one window under the mix,
checks a sample of what the window served against the reference and
prints one JSON line.  With ``trace`` the window runs under the JAX
profiler and ``repro.obs`` tracing, and the line carries the per-layer
metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import loadgen
import trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: requests due in the window get this long past its close to finish
SETTLE_S = 60.0
#: the profiler records the window's last seconds only: every operation of
#: the scan is an event, and a longer trace outgrows the profiler's buffer
#: (a 20 s trace of the flat L2 plan kept only its first 9 s) and takes
#: minutes to collect
TRACE_S = 3.0


class NoChip(RuntimeError):
    """JAX found no device the benchmark knows, or too few of them."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(workload: str, root: str = ROOT) -> tuple:
    """``(benchmark, cell, config, traffic)`` for a cell name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "config")
    cfg = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, cfg, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries a cell reports: its end-to-end metrics
    (``trace`` off) or its per-layer ones (``trace`` on).  A metric
    without ``workloads`` belongs to every cell that reports the
    end-to-end metric it moves (end-to-end: to every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def seed_streams(seed: int) -> dict:
    """Independent generators of one ``--seed`` (any non-negative
    integer, of any size): host data, traffic, the checked sample, and
    the two words of the device key."""
    ss = np.random.SeedSequence(int(seed))
    pool, traffic, sample, dev = ss.spawn(4)
    return {"pool": np.random.default_rng(pool),
            "traffic": np.random.default_rng(traffic),
            "sample": np.random.default_rng(sample),
            "key": dev.generate_state(2, dtype=np.uint32)}


def device_info(jax, chips: int, peaks_doc: dict) -> tuple:
    """``(device dict, peaks)`` of the devices JAX found; raises
    :class:`NoChip` unless they are accelerators in the peaks table and
    at least ``chips`` of them."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform {d0.platform})")
    peaks = peaks_doc["devices"].get(d0.device_kind)
    if peaks is None:
        raise NoChip(f"device kind {d0.device_kind!r} is not in "
                     f"bench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, peaks)


def enable_compile_cache(jax, root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, so only a cell's first run there
    compiles."""
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def warm_row_counts(server, pool: np.ndarray) -> None:
    """Serve one request of each row count an open loop of the pool's
    blocks can coalesce into, up to the server's micro-batch, through
    the server's own path, so that whatever a partial micro-batch
    compiles is compiled in set-up."""
    rows = pool.shape[1]
    for m in range(rows, server.max_batch + 1, rows):
        server.search(np.concatenate([pool[j % len(pool)]
                                      for j in range(m // rows)]))


class CompileCounter:
    """Counts compilations (persistent-cache loads included) while
    ``active``: the window should see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _one = None

    @classmethod
    def get(cls, jax) -> "CompileCounter":
        """The process's one counter (a listener cannot be removed)."""
        if cls._one is None:
            cls._one = cls(jax)
        cls._one.count, cls._one.seconds = 0, 0.0
        return cls._one

    def __init__(self, jax):
        self.active = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.active and event == self.EVENT:
            self.count += 1
            self.seconds += duration


@dataclass
class Context:
    """What the metric readers read.  Times are ``perf_counter``
    seconds; ``trace`` is the reduced device trace (``None`` unless
    traced or when it holds no device)."""

    cfg: dict
    kind: Any
    traffic: dict
    cell: dict
    peaks: dict
    seconds: float
    setup_s: float
    t0: float
    t1: float
    gave_up: float
    records: list
    stats0: dict
    stats1: dict
    spans: dict = field(default_factory=dict)
    trace: Any = None


def _process_start_s() -> Optional[float]:
    try:
        import psutil
        return psutil.Process().create_time()
    except Exception:                       # noqa: BLE001 — optional
        return None


def use_system(root: str = ROOT) -> None:
    """Put the system under test (``<root>/src``) on the import path;
    raises ``FileNotFoundError`` where it is missing."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the system under test is missing: "
                                f"{src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_kind(cfg: dict):
    return load_module(os.path.join(BENCH_DIR, "kinds", cfg["kind"] + ".py"),
                       "bench_kind_" + cfg["kind"])


def start_server(jax, cfg: dict, traffic: dict, seed: int,
                 seconds: float) -> tuple:
    """The set-up every run shares: data from the seed, the program
    built, served by a ``CamSearchServer`` with its defaults, and every
    shape the mix will send compiled and run once.  Returns ``(kind,
    streams, gallery, program, pool, server)``; the server is running."""
    from repro.serving import CamSearchServer

    kind = load_kind(cfg)
    streams = seed_streams(seed)
    key = jax.random.wrap_key_data(np.asarray(streams["key"]))
    gallery = kind.make_gallery(cfg, key)
    gallery.block_until_ready()
    program = kind.build(cfg)
    rows = int(traffic["rows"])
    pool = kind.make_queries(cfg, streams["pool"],
                             loadgen.pool_size(traffic, seconds), rows)
    server = CamSearchServer(program, gallery).start()
    try:
        for j in range(2):                   # prepare + compile, then warm
            server.search(pool[j % len(pool)])
        if rows < server.max_batch:
            warm_row_counts(server, pool)
    except BaseException:
        server.stop()
        raise
    return kind, streams, gallery, program, pool, server


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_start: Optional[float] = None,
             require_chip: bool = True, compile_cache: bool = True,
             overrides: Optional[dict] = None,
             before_window: Optional[Callable[[Any], None]] = None,
             trace_s: float = TRACE_S, keep_trace: Optional[str] = None,
             log: Callable[[str], None] = lambda s: print(
                 s, file=sys.stderr, flush=True)) -> dict:
    """Run one cell; returns the result dict.  Given ``t_start`` (the
    entry point's first ``perf_counter`` reading), set-up is counted from
    the process's start, or from ``t_start`` where that cannot be read;
    otherwise from this call.  ``require_chip=False``,
    ``overrides`` (config keys) and ``before_window(server)`` exist for
    the CPU tests, which drive a run at small sizes with the timed path
    broken underneath.  ``trace_s`` is how much of the window's end the
    profiler records; ``keep_trace`` a directory that keeps the traced
    run's profile and what its reduction read and gave
    (``tools/record_trace.py``)."""
    wall_start = _process_start_s() if t_start is not None else None
    perf_start = time.perf_counter() if t_start is None else t_start
    bench, cell, cfg, traffic = load_cell(workload, root)
    cfg = {**cfg, **(overrides or {})}
    use_system(root)

    import jax

    peaks_doc = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if require_chip:
        device, peaks = device_info(jax, int(cell["chips"]), peaks_doc)
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": jax.device_count()}
        peaks = next(iter(peaks_doc["devices"].values()))
    if compile_cache:
        enable_compile_cache(jax, root)
    counter = CompileCounter.get(jax)

    from repro.core import clear_plan_cache

    kind, streams, gallery, program, pool, server = start_server(
        jax, cfg, traffic, seed, seconds)
    plan = server.plan
    try:
        if before_window is not None:
            before_window(server)
        result = _window(jax, server, pool, traffic, seconds, trace,
                         streams, counter, root, log, trace_s, keep_trace)
    finally:
        server.stop()
    now_wall, now_perf = time.time(), time.perf_counter()
    setup_s = (result["t0"] - now_perf + now_wall - wall_start
               if wall_start is not None else result["t0"] - perf_start)
    peak = _memory_peak(jax)
    stats0, stats1 = result["stats0"], result["stats1"]
    degraded = result["stats_end"]["degraded_batches"]
    log(f"plan backend={plan.backend} packed={plan.packed} "
        f"batch={plan.batch} shards={plan.shards}")

    # the program's state goes before the reference runs
    del server, program, plan
    clear_plan_cache()
    gc.collect()

    records = result["records"]
    checks, sample_n = _check(kind, cfg, gallery, pool, records,
                              streams["sample"])
    checks["degraded_batches"] = (degraded, 0)
    ctx = Context(cfg=cfg, kind=kind, traffic=traffic, cell=cell,
                  peaks=peaks, seconds=seconds, setup_s=setup_s, t0=result["t0"],
                  t1=result["t1"], gave_up=result["gave_up"],
                  records=records, stats0=stats0, stats1=stats1,
                  spans=result.get("spans", {}), trace=result.get("trace"))
    metrics = {}
    readers_dir = os.path.join(BENCH_DIR, "metrics")
    if readers_dir not in sys.path:
        sys.path.insert(0, readers_dir)     # the readers' shared helpers
    for m in cell_metrics(bench, workload, trace):
        reader = load_module(os.path.join(readers_dir, m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    failed = sum(1 for r in records if r.latency_s() == math.inf)
    correct = all(_within(v, lim) for v, lim in checks.values())
    if trace and ctx.trace is not None:
        device = {**device, "busy_s": ctx.trace.busy_s,
                  "window_s": ctx.trace.window_s}
    device["memory_peak_bytes"] = peak
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and ctx.trace is not None:
        out["breakdown"] = ctx.trace.breakdown()
    lates = [r.late_s for r in records]
    out["run"] = {"seed": int(seed), "seconds": seconds,
                  "setup_s": setup_s, "checked_rows": sample_n,
                  "compiles_in_window": result["compiles"],
                  "compile_s_in_window": result["compile_s"],
                  "generator_late_max_s": max(lates) if lates else 0.0,
                  "generator_late_mean_s":
                      float(np.mean(lates)) if lates else 0.0}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, (v, lim) in checks.items()}
    log(f"run {json.dumps(out['run'])}")
    for name, (v, lim) in checks.items():
        log(f"check {name} {v!r} limit {lim!r}")
    return out


def _within(value, limit) -> bool:
    return limit is not None and bool(value <= limit)


def _memory_peak(jax) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _window(jax, server, pool, traffic, seconds, trace, streams, counter,
            root, log, trace_s=TRACE_S, keep=None) -> dict:
    """Measure one window; with ``trace``, under ``repro.obs`` tracing
    throughout and the profiler over its last ``trace_s`` seconds.
    Returns the records and what the readers need."""
    from repro import obs

    prof: Dict[str, Any] = {}
    stats0 = dict(server.stats)
    t0 = time.perf_counter() + 0.05
    if trace:
        scratch = os.path.join(root, ".bench_traces")
        os.makedirs(scratch, exist_ok=True)
        prof["dir"] = tempfile.mkdtemp(prefix="run-", dir=scratch)
        obs.tracer.clear()
        obs.enable(capacity=1 << 20)
        obs.instant("bench.sync", "serving")
        obs_sync = time.perf_counter_ns()

        def start_profiler() -> None:
            # no Python tracer: an event per Python call slows the host
            # path it would watch, and the reduction reads none of them
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof["dir"], profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.sync"):
                prof["sync"] = time.perf_counter_ns()

        starter = threading.Timer(t0 + max(0.0, seconds - trace_s)
                                  - time.perf_counter(), start_profiler)
        starter.start()
    counter.active = True
    records = loadgen.run(server, pool, traffic, seconds,
                          streams["traffic"], t0)
    t1 = t0 + seconds
    rest = t1 - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    stats1 = dict(server.stats)
    counter.active = False
    out: Dict[str, Any] = {"t0": t0, "t1": t1, "stats0": stats0,
                           "stats1": stats1, "compiles": counter.count,
                           "compile_s": counter.seconds}
    if trace:
        starter.join()
        jax.profiler.stop_trace()
        obs.stop()
        # the profiler records from its start, which takes tens of
        # milliseconds: the traced window opens at the sync annotation
        # written once it has started
        prof["t0"] = prof["sync"] / 1e9
    out["gave_up"] = t1 + SETTLE_S
    loadgen.settle(records, out["gave_up"])
    out["stats_end"] = dict(server.stats)
    out["records"] = records
    if trace:
        chrome = obs.to_chrome()
        out["spans"], intervals = trace_reduce.host_spans(chrome, obs_sync,
                                                          t0, t1)
        paths = sorted(glob.glob(os.path.join(prof["dir"], "**",
                                              "*.xplane.pb"), recursive=True))
        if paths:
            t = time.perf_counter()
            out["trace"] = trace_reduce.reduce_xplane(
                paths[-1], sync=prof["sync"], t0=prof["t0"], t1=t1,
                intervals=intervals)
            log(f"trace read in {time.perf_counter() - t:.3f} s from "
                f"{os.path.getsize(paths[-1])} bytes")
            if keep is not None and out["trace"] is not None:
                keep_profile(keep, paths[-1], prof, t1, intervals,
                             out["trace"])
        shutil.rmtree(prof["dir"], ignore_errors=True)
    return out


def keep_profile(keep: str, xplane: str, prof: dict, t1: float,
                 intervals: list, reduced) -> None:
    """Keep a traced run's profile, gzipped, beside what its reduction
    read (the sync instant, the traced window, the host spans open in
    it) and what it gave."""
    import gzip

    os.makedirs(keep, exist_ok=True)
    with open(xplane, "rb") as src, \
            gzip.open(os.path.join(keep, "trace.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    doc = {"sync": prof["sync"], "t0": prof["t0"], "t1": t1,
           "intervals": [iv for iv in intervals
                         if iv[2] >= prof["t0"] and iv[1] <= t1],
           "reduced": {"window_s": reduced.window_s,
                       "busy_s": reduced.busy_s,
                       "devices": reduced.devices,
                       "modules": reduced.modules,
                       "breakdown": reduced.breakdown()}}
    with open(os.path.join(keep, "trace.json"), "w") as f:
        json.dump(doc, f, indent=1)


def _check(kind, cfg, gallery, pool, records, rng) -> tuple:
    """Compare a sample of the rows the window served, drawn from the
    seed, with the reference.  Returns ``({name: (value, limit)},
    rows checked)``."""
    limits = cfg["limits"]
    failed = sum(1 for r in records if r.latency_s() == math.inf)
    checks = {"failed_requests": (failed, limits["failed_requests"])}
    served = [(i, j) for i, r in enumerate(records)
              if r.latency_s() != math.inf for j in range(r.rows)]
    if not served:
        checks["served_rows"] = (0, None)
        return checks, 0
    take = min(int(cfg["check_rows"]), len(served))
    pick = sorted(rng.choice(len(served), size=take, replace=False))
    queries, values, ids = [], [], []
    for p in pick:
        i, j = served[p]
        rec = records[i]
        res = rec.outcome()
        queries.append(pool[rec.pool_index][j])
        values.append(res.values[j])
        ids.append(res.indices[j])
    queries = np.stack(queries)
    ref = kind.reference(cfg, gallery, queries)
    numbers = kind.compare(cfg, gallery, queries, np.stack(values),
                           np.stack(ids), ref)
    for name, v in numbers.items():
        checks[name] = (v, limits.get(name))
    return checks, take
