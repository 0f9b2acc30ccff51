"""Observability: tracer spans, metrics registry, Perfetto export.

Covers the obs contract the serving stack now leans on: span nesting and
late attributes, the zero-cost disabled mode, histogram percentiles
against numpy's exact answer, the Chrome/Perfetto JSON schema round-trip
(valid and corrupted), and span presence in real FrameEngine/VideoEngine
runs — the four instrumented layers (cache, compile/ILP, autotune,
engine step/executor) must all show up in one enabled run.
"""
import json
import threading

import numpy as np
import pytest

from repro.imaging import FrameEngine, FrameRequest, PlanCache
from repro.imaging.metrics import EngineMetrics
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Tracer,
                       export, trace)
from repro.obs.metrics import UNIT_BUCKETS
from repro.obs.trace import NULL_SPAN
from repro.video import VideoEngine, VideoFrame

RNG = np.random.RandomState(7)


@pytest.fixture
def global_trace():
    """Enable the process-global tracer for a test; always restore."""
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.clear()


# ------------------------------------------------------------------ tracer
def test_span_nesting_depth_parent_attrs():
    tr = Tracer(enabled=True)
    with tr.span("outer", pipeline="unsharp-m"):
        with tr.span("middle", w=64) as sp:
            sp.set(late=True, n=3)
            with tr.span("inner"):
                pass
    evs = {e.name: e for e in tr.events()}
    assert set(evs) == {"outer", "middle", "inner"}
    assert (evs["outer"].depth, evs["outer"].parent) == (0, None)
    assert (evs["middle"].depth, evs["middle"].parent) == (1, "outer")
    assert (evs["inner"].depth, evs["inner"].parent) == (2, "middle")
    assert evs["outer"].attrs == {"pipeline": "unsharp-m"}
    assert evs["middle"].attrs == {"w": 64, "late": True, "n": 3}
    # completion order: inner exits first, outer last
    assert [e.name for e in tr.events()] == ["inner", "middle", "outer"]
    # children are contained in the parent's interval
    for child, parent in (("inner", "middle"), ("middle", "outer")):
        c, p = evs[child], evs[parent]
        assert p.ts_ns <= c.ts_ns
        assert c.ts_ns + c.dur_ns <= p.ts_ns + p.dur_ns


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("never", pipeline="x")
    assert sp is NULL_SPAN            # shared singleton: no allocation
    with sp as s:
        s.set(anything=1)             # attribute set is swallowed
    assert tr.events() == []
    assert len(tr) == 0
    # module-level fast path returns the same singleton when disabled
    assert not trace.enabled()
    assert trace.span("never") is NULL_SPAN


def test_traced_decorator():
    tr = Tracer(enabled=True)

    @tr.traced("work.unit", kind="test")
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    evs = tr.events()
    assert [e.name for e in evs] == ["work.unit"] * 2
    assert all(e.attrs == {"kind": "test"} for e in evs)

    @tr.traced()
    def unnamed():
        return 42

    assert unnamed() == 42
    assert tr.events()[-1].name.endswith("unnamed")


def test_ring_buffer_capacity_drops_oldest():
    tr = Tracer(enabled=True, capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [e.name for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    tr.clear()
    assert tr.events() == []
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_span_exit_threadsafe():
    tr = Tracer(enabled=True)

    def worker(k):
        for i in range(50):
            with tr.span(f"t{k}", i=i):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 200              # no event lost to a race
    for k in range(4):
        assert sum(e.name == f"t{k}" for e in evs) == 50
    assert all(e.depth == 0 for e in evs)   # stacks are thread-local


# ----------------------------------------------------------------- metrics
def test_histogram_percentiles_vs_numpy():
    rng = np.random.RandomState(0)
    # lognormal latencies spanning several exponential buckets
    xs = rng.lognormal(mean=-7.0, sigma=1.5, size=2000)
    h = Histogram("lat")
    for x in xs:
        h.observe(float(x))
    for q in (50.0, 95.0, 99.0):
        exact = float(np.percentile(xs, q))
        est = h.percentile(q)
        # the estimate must land within the bucket that contains the
        # exact answer — bucket bounds are factor-2, so 2x each way
        assert exact / 2 <= est <= exact * 2, (q, exact, est)
    snap = h.snapshot()
    assert snap["count"] == 2000
    assert snap["mean"] == pytest.approx(xs.mean())
    assert snap["max"] == pytest.approx(xs.max())
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_histogram_edge_cases():
    h = Histogram("h", buckets=UNIT_BUCKETS)
    assert h.snapshot() == {"count": 0, "mean": 0.0, "max": 0.0, "min": 0.0,
                            "p50": 0.0, "p95": 0.0, "p99": 0.0}
    h.observe(0.5)
    # single sample: every percentile is that sample (clamped to min/max)
    assert h.percentile(1.0) == h.percentile(99.0) == 0.5
    h2 = Histogram("h2")
    h2.observe(1e9)                   # beyond the last bound: +Inf bucket
    assert h2.percentile(50.0) == 1e9
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", buckets=(2.0, 1.0))


def test_registry_get_or_create_and_type_check():
    reg = MetricsRegistry()
    c = reg.counter("frames", help="h")
    assert reg.counter("frames") is c
    assert isinstance(c, Counter)
    c.inc()
    c.inc(4)
    g = reg.gauge("vmem")
    g.set_max(10)
    g.set_max(3)
    assert isinstance(g, Gauge) and g.value == 10
    reg.histogram("lat").observe(0.01)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("frames")
    assert "frames" in reg and "nope" not in reg
    snap = reg.snapshot()
    assert snap["frames"] == 5 and snap["vmem"] == 10
    assert snap["lat"]["count"] == 1


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("eng_frames", help="frames served").inc(3)
    reg.gauge("eng_vmem").set(1024)
    h = reg.histogram("eng_lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus_text()
    assert "# HELP eng_frames frames served" in text
    assert "# TYPE eng_frames counter" in text
    assert "eng_frames 3" in text
    assert "# TYPE eng_vmem gauge" in text
    assert 'eng_lat_bucket{le="0.1"} 1' in text      # cumulative counts
    assert 'eng_lat_bucket{le="1"} 2' in text
    assert 'eng_lat_bucket{le="+Inf"} 3' in text
    assert "eng_lat_count 3" in text


def test_engine_metrics_reconciliation():
    m = EngineMetrics(prefix="t")
    m.frames_submitted += 5
    m.observe_batch("unsharp-m", n_frames=3, slots=4, execute_s=0.01,
                    vmem_bytes=100, rows_per_step=4)
    m.frames_rejected += 2
    assert m.in_flight == 2           # submitted == completed + in_flight
    snap = m.snapshot()
    assert snap["frames_submitted"] == 5
    assert snap["frames_completed"] == 3
    assert snap["frames_in_flight"] == 2
    assert snap["frames_rejected"] == 2   # outside the identity
    # the set-backed rows_per_step view stays sorted and deduplicated
    m.observe_batch("unsharp-m", 1, 4, 0.01, 100, rows_per_step=1)
    m.observe_batch("unsharp-m", 1, 4, 0.01, 100, rows_per_step=4)
    assert m.snapshot()["rows_per_step_seen"] == [1, 4]
    assert isinstance(m.rows_per_step_seen, set)
    # counters live in the registry under the prefix
    assert m.registry.snapshot()["t_frames_submitted"] == 5


def test_shared_registry_telemetry_plane():
    """One registry across engine metrics + cache = one scrape."""
    reg = MetricsRegistry()
    eng_m = EngineMetrics(registry=reg, prefix="frame_engine")
    cache = PlanCache(registry=reg)
    eng_m.frames_submitted += 1
    cache.stats.plan_misses += 1
    snap = reg.snapshot()
    assert snap["frame_engine_frames_submitted"] == 1
    assert snap["plan_cache_plan_misses"] == 1
    text = reg.to_prometheus_text()
    assert "frame_engine_frames_submitted 1" in text
    assert "plan_cache_plan_misses 1" in text


def test_plan_cache_snapshot_merges_everything():
    cache = PlanCache()
    cache.plan_for("unsharp-m", 32)
    snap = cache.snapshot()
    for key in ("plan_hits", "plan_misses", "plans_resident",
                "execs_resident", "tunings_resident", "max_plans",
                "max_execs", "vmem_bytes"):
        assert key in snap, key
    assert snap["plan_misses"] == 1 and snap["plans_resident"] == 1
    cache.plan_for("unsharp-m", 32)
    assert cache.snapshot()["plan_hits"] == 1


# ------------------------------------------------------------------ export
def test_chrome_trace_round_trip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("a", pipeline="p", w=32):
        with tr.span("b", n=np.int64(3), f=np.float32(0.5)):
            pass
    data = export.to_chrome_trace(tr.events(), process_name="test")
    assert export.validate_trace(data) == []
    path = tmp_path / "t.json"
    export.write_trace(str(path), data)
    loaded = export.load_trace(str(path))
    assert export.validate_trace(loaded) == []
    json.dumps(loaded)                               # fully JSON-able
    spans = {e["name"]: e for e in loaded["traceEvents"]
             if e["ph"] == "X"}
    assert set(spans) == {"a", "b"}
    assert spans["b"]["args"]["parent"] == "a"
    assert spans["b"]["args"]["depth"] == 1
    assert spans["b"]["args"]["n"] == 3              # numpy coerced
    assert spans["a"]["args"]["pipeline"] == "p"
    meta = [e for e in loaded["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "test"


def test_validate_trace_rejects_corruption():
    tr = Tracer(enabled=True)
    with tr.span("a"):
        pass
    good = export.to_chrome_trace(tr.events())
    assert export.validate_trace("not a dict")
    assert export.validate_trace({}) == ["missing or non-list 'traceEvents'"]
    bad = json.loads(json.dumps(good))
    bad["otherData"]["schema"] = "wrong/v9"
    assert any("schema" in e for e in export.validate_trace(bad))
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][1]["dur"] = -5.0
    assert any("dur" in e for e in export.validate_trace(bad))
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][1]["ph"] = "Q"
    assert any("ph" in e for e in export.validate_trace(bad))
    bad = json.loads(json.dumps(good))
    bad["traceEvents"] = [e for e in bad["traceEvents"] if e["ph"] != "X"]
    assert any("no complete" in e for e in export.validate_trace(bad))


def test_flame_summary_self_time():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    data = export.to_chrome_trace(tr.events())
    text = export.flame_summary(data)
    assert "outer" in text and "inner" in text and "self ms" in text
    # outer's self time excludes inner: spot-check the arithmetic
    spans = [e for e in data["traceEvents"] if e["ph"] == "X"]
    self_us = dict(zip([e["name"] for e in spans],
                       export._self_times_us(spans)))
    durs = {e["name"]: e["dur"] for e in spans}
    assert self_us["inner"] == pytest.approx(durs["inner"])
    assert self_us["outer"] == pytest.approx(durs["outer"] - durs["inner"])
    assert export.flame_summary({"traceEvents": []}) == "(no spans)"


def test_export_global_trace(tmp_path, global_trace):
    with trace.span("solo", k=1):
        pass
    path = tmp_path / "g.json"
    data = export.export_global_trace(str(path), process_name="gtest")
    assert path.exists()
    assert export.validate_trace(data) == []
    names = [e["name"] for e in data["traceEvents"] if e["ph"] == "X"]
    assert names == ["solo"]


# ----------------------------------------------------- engine integration
def _frame_req(rid, name="unsharp-m", shape=(24, 32)):
    return FrameRequest(rid=rid, pipeline=name,
                        frames={"in": RNG.rand(*shape).astype(np.float32)})


def test_frame_engine_emits_spans(global_trace):
    eng = FrameEngine(max_batch=2, max_pending=8)
    done = eng.run([_frame_req(i) for i in range(3)])
    assert len(done) == 3
    names = {e.name for e in trace.events()}
    # all four instrumented layers show up from one cold engine drain
    assert {"engine.step", "engine.assemble", "engine.execute",
            "executor.call", "cache.plan", "cache.exec",
            "compile.pipeline", "ilp.build_problem",
            "ilp.solve"} <= names
    steps = [e for e in trace.events() if e.name == "engine.step"]
    assert steps and all(e.attrs["engine"] == "frame" for e in steps)
    assert all(e.attrs["pipeline"] == "unsharp-m" for e in steps)
    assert all(e.attrs["queue_wait_s"] >= 0 for e in steps)
    assert all("execute_s" in e.attrs for e in steps)
    # nesting: execute is a child of step, executor.call a child of execute
    execs = [e for e in trace.events() if e.name == "engine.execute"]
    assert all(e.parent == "engine.step" and e.depth == 1 for e in execs)
    calls = [e for e in trace.events() if e.name == "executor.call"]
    assert all(e.parent == "engine.execute" for e in calls)
    # engine snapshot merges metrics + cache views
    snap = eng.snapshot()
    assert snap["frames_completed"] == 3
    assert snap["cache"]["plans_resident"] >= 1
    # and the whole run exports as a valid Perfetto trace
    data = export.to_chrome_trace(trace.events())
    assert export.validate_trace(data) == []


def test_video_engine_emits_spans(global_trace):
    eng = VideoEngine(chunk=2)
    sid = eng.open_stream("tmotion-t", 24, 32)
    fed, outs = 0, []
    while fed < 6 or eng.pending:
        while fed < 6 and eng.submit(
                VideoFrame(sid, {"in": RNG.rand(24, 32).astype(np.float32)})):
            fed += 1
        outs.extend(eng.step())
    assert len(outs) == 6
    names = {e.name for e in trace.events()}
    assert {"engine.step", "engine.execute", "executor.call",
            "cache.plan", "compile.pipeline"} <= names
    steps = [e for e in trace.events() if e.name == "engine.step"]
    assert all(e.attrs["engine"] == "video" for e in steps)
    assert all(e.attrs["pipeline"] == "tmotion-t" for e in steps)
    eng.close_stream(sid)
    snap = eng.snapshot()
    assert snap["frames_completed"] == 6
    assert "cache" in snap and "pending" in snap


def test_engines_silent_when_tracing_disabled():
    assert not trace.enabled()
    trace.clear()
    eng = FrameEngine(max_batch=2, max_pending=8)
    assert len(eng.run([_frame_req(0)])) == 1
    assert trace.events() == []       # zero spans recorded


def test_compile_span_counts_the_dag(global_trace):
    """``compile.pipeline`` carries the DAG's stages, edges, multi-consumer
    stages and window taps; with tracing off the taps are not counted."""
    from repro.core import algorithms, codegen
    codegen.compile_pipeline(algorithms.canny_m(), 32)
    (sp,) = [e for e in trace.events() if e.name == "compile.pipeline"]
    assert {k: sp.attrs[k] for k in ("stages", "edges", "mc_stages",
                                     "taps")} == {
        "stages": 10, "edges": 10, "mc_stages": 1, "taps": 38}
    trace.disable()
    dag = algorithms.canny_m()
    codegen.compile_pipeline(dag, 32)
    assert "taps" not in vars(dag)      # the cached count was never made
