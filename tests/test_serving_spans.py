"""The serving spans of both engines: the batch assembly split into its
host -> device copy (``engine.h2d``) and its stack (``engine.stack``),
output delivery (``engine.deliver``), their attributes, their place in
the span tree, and their profiler annotations, which share the device
trace's clock."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.core import algorithms
from repro.imaging import FrameEngine, FrameRequest
from repro.kernels.stencil_pipeline import (VideoExecutor, make_executor,
                                            make_video_executor)
from repro.obs import trace
from repro.perf.measure import step_breakdown
from repro.video import VideoEngine, VideoFrame

H, W = 24, 32
FRAME_BYTES = H * W * 4
SERVING = {"engine.step", "engine.assemble", "engine.h2d", "engine.stack",
           "engine.execute", "engine.deliver", "executor.call"}


@pytest.fixture
def global_trace():
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.clear()


def _frames(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.rand(H, W).astype(np.float32) for _ in range(n)]


def _serve_frames(n, max_batch=2):
    eng = FrameEngine(max_batch=max_batch, max_pending=8)
    done = eng.run([FrameRequest(rid=i, pipeline="unsharp-m",
                                 frames={"in": f})
                    for i, f in enumerate(_frames(n))])
    assert len(done) == n


def _serve_video(n, chunk=2):
    """``n`` frames of one tbackground-t stream, submitted at once."""
    eng = VideoEngine(chunk=chunk)
    sid = eng.open_stream("tbackground-t", H, W)
    for f in _frames(n):
        assert eng.submit(VideoFrame(sid, {"in": f})) is True
    outs = []
    while eng.pending:
        outs += eng.step()
    assert len(outs) == n


def _named(name):
    return [e for e in trace.events() if e.name == name]


def _within(child, parent):
    return (child.tid == parent.tid and parent.ts_ns <= child.ts_ns
            and child.ts_ns + child.dur_ns <= parent.ts_ns + parent.dur_ns)


def _check_tree(batches):
    """Each batch: step > {assemble > {h2d, stack}, execute, deliver}."""
    steps = _named("engine.step")
    assert len(steps) == len(batches)
    for name in ("engine.h2d", "engine.stack"):
        spans = _named(name)
        assert len(spans) == len(batches)
        assert all(e.parent == "engine.assemble" and e.depth == 2
                   for e in spans)
        assert all(any(_within(e, a) for a in _named("engine.assemble"))
                   for e in spans)
    delivers = _named("engine.deliver")
    assert [e.attrs["frames"] for e in delivers] == batches
    assert all(e.parent == "engine.step" and e.depth == 1
               for e in delivers)
    assert all(any(_within(e, s) for s in steps) for e in delivers)
    for step in steps:       # copy, then stack, then execute, then deliver
        order = [e.name for e in sorted(
            (e for e in trace.events() if _within(e, step) and e is not step
             and e.name in SERVING - {"executor.call", "engine.assemble"}),
            key=lambda e: e.ts_ns)]
        assert order == ["engine.h2d", "engine.stack", "engine.execute",
                         "engine.deliver"]


def test_frame_engine_batch_spans(global_trace):
    _serve_frames(3, max_batch=2)
    _check_tree([2, 1])
    h2d = _named("engine.h2d")
    assert [e.attrs["frames"] for e in h2d] == [2, 1]
    assert [e.attrs["bytes"] for e in h2d] == [2 * FRAME_BYTES, FRAME_BYTES]
    assert all(e.attrs["pipeline"] == "unsharp-m"
               for e in h2d + _named("engine.deliver"))


def test_frame_engine_prefetched_batch_spans(global_trace):
    """Three full batches queued at once: each batch has one assembly,
    one call and one delivery; the second and third are assembled while
    the batch before them runs, inside its ``engine.execute``."""
    _serve_frames(6, max_batch=2)
    steps = _named("engine.step")
    assembles = _named("engine.assemble")
    executes = _named("engine.execute")
    assert len(steps) == len(assembles) == len(executes) == 3
    assert len(_named("engine.h2d")) == len(_named("engine.stack")) == 3
    assert [e.attrs["frames"] for e in _named("engine.deliver")] == [2] * 3
    assert [a.attrs["prefetched"] for a in assembles] == [False, True, True]
    first, *later = assembles
    assert first.parent == "engine.step" and first.depth == 1
    assert _within(first, steps[0])
    for a, ex in zip(later, executes):    # batch k + 1 under call k
        assert a.parent == "engine.execute" and a.depth == 2
        assert _within(a, ex)
    for name in ("engine.h2d", "engine.stack"):
        assert all(e.parent == "engine.assemble" and
                   any(_within(e, a) for a in assembles)
                   for e in _named(name))


def test_video_engine_chunk_spans(global_trace):
    _serve_video(4, chunk=2)
    _check_tree([2, 2])
    h2d = _named("engine.h2d")
    assert [e.attrs["frames"] for e in h2d] == [2, 2]
    assert [e.attrs["bytes"] for e in h2d] == [2 * FRAME_BYTES] * 2


def test_video_single_frame_copies_before_the_call(global_trace):
    _serve_video(1, chunk=2)             # one frame: the single-frame call
    (h2d,) = _named("engine.h2d")
    (call,) = _named("executor.call")
    assert h2d.parent == "engine.step" and h2d.depth == 1
    assert (h2d.attrs["frames"], h2d.attrs["bytes"]) == (1, FRAME_BYTES)
    assert h2d.ts_ns + h2d.dur_ns <= call.ts_ns
    assert not _named("engine.stack") and not _named("engine.deliver")


def test_both_engines_trace_one_transfer_path(global_trace):
    """The same two frames, as one FrameEngine batch and as one
    VideoEngine chunk, open their copy, stack and delivery spans with
    the same attributes."""
    frames = _frames(2)

    def serve_video():
        eng = VideoEngine(chunk=2)
        sid = eng.open_stream("unsharp-m", H, W)
        eng.run({sid: [{"in": f} for f in frames]})

    seen = {}
    for kind, serve in [
            ("frame", lambda: FrameEngine(max_batch=2).run(
                [FrameRequest(rid=i, pipeline="unsharp-m",
                              frames={"in": f})
                 for i, f in enumerate(frames)])),
            ("video", serve_video)]:
        trace.clear()
        serve()
        seen[kind] = {name: [e.attrs for e in _named(name)]
                      for name in ("engine.h2d", "engine.stack",
                                   "engine.deliver")}
    assert seen["frame"] == seen["video"]
    (h2d,), (deliver,) = seen["frame"]["engine.h2d"], \
        seen["frame"]["engine.deliver"]
    assert h2d["bytes"] == 2 * 1 * H * W * 4     # frames x feeds x h x w
    assert (deliver["frames"], deliver["programs"]) == (2, 1)


def test_serving_spans_silent_when_tracing_disabled():
    assert not trace.enabled()
    trace.clear()
    _serve_frames(3)
    _serve_video(3)
    assert trace.events() == []


@pytest.mark.parametrize("serve", [_serve_frames, _serve_video],
                         ids=["frame", "video"])
def test_serving_spans_are_profiler_annotations(global_trace, tmp_path,
                                                serve):
    """Every serving span reaches the profiler's host plane, where the
    benchmark's trace reduction names device idle gaps after it."""
    from bench import xtrace
    serve(4)                              # compile outside the profile
    trace.clear()
    with jax.profiler.trace(str(tmp_path)):
        serve(4)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    anns = xtrace.load(path).annotations
    assert SERVING <= {a.name for a in anns}
    # the copy and the stack lie inside an assembly on the trace's clock
    asm = [a for a in anns if a.name == "engine.assemble"]
    for a in anns:
        if a.name in ("engine.h2d", "engine.stack"):
            assert any(p.start <= a.start and a.end <= p.end for p in asm)


@pytest.mark.parametrize("make,lead,name", [
    (lambda: make_executor(algorithms.canny_m(), 16, 128, batch=2,
                           rows_per_step=8),
     (2,), "imagen_frame_batch_canny_m"),
    (lambda: make_executor(algorithms.canny_m(), 16, 128, rows_per_step=8),
     (), "imagen_frame_canny_m"),
    (lambda: make_video_executor(algorithms.VIDEO_ALGORITHMS[
        "tbackground-t"](), 16, 128, rows_per_step=8, chunk=2),
     (2,), "imagen_video_step_tbackground_t"),
])
def test_executor_programs_carry_stable_names(make, lead, name):
    ex = make()
    args = ({"in": jax.ShapeDtypeStruct(lead + (16, 128), np.float32)},)
    if isinstance(ex, VideoExecutor):
        args += (ex.init_state(),)
    text = ex._fn.lower(*args).as_text()
    assert text.splitlines()[0].startswith(f"module @jit_{name} ")


def _chrome(spans):
    """A Chrome trace of complete events: (name, ts_us, dur_us, tid)."""
    return {"traceEvents": [
        {"ph": "X", "name": n, "ts": ts, "dur": dur, "pid": 1, "tid": tid,
         "args": {"pipeline": "p", "queue_wait_s": 0.001}}
        for n, ts, dur, tid in spans]}


def test_step_breakdown_parts_partition_the_step():
    data = _chrome([
        ("engine.step", 0, 100, 1),
        ("engine.assemble", 5, 30, 1),
        ("engine.h2d", 6, 20, 1),
        ("engine.stack", 27, 7, 1),
        ("engine.execute", 40, 40, 1),
        ("executor.call", 41, 10, 1),
        ("engine.deliver", 82, 12, 1),
        ("engine.step", 200, 50, 1),
        ("engine.deliver", 210, 30, 1),
    ])
    b = step_breakdown(data, "p")
    assert b["n_steps"] == 2
    assert b["step_s"] == pytest.approx(150e-6)
    assert b["queue_wait_s"] == pytest.approx(0.002)
    assert b["assemble_s"] == pytest.approx(30e-6)
    assert b["execute_s"] == pytest.approx(40e-6)
    assert b["deliver_s"] == pytest.approx(42e-6)
    # self time: 100 - 30 - 40 - 12 and 50 - 30 (h2d, stack and the
    # call are grandchildren)
    assert b["step_self_s"] == pytest.approx(38e-6)
    parts = ("assemble_s", "execute_s", "deliver_s", "step_self_s")
    assert sum(b[k] for k in parts) == pytest.approx(b["step_s"])


def test_step_breakdown_counts_a_prefetched_assembly_once():
    """A batch assembled inside the previous call counts as assembly,
    not also as execution."""
    data = _chrome([
        ("engine.step", 0, 100, 1),
        ("engine.assemble", 5, 30, 1),
        ("engine.h2d", 6, 20, 1),
        ("engine.stack", 27, 7, 1),
        ("engine.execute", 40, 40, 1),
        ("executor.call", 41, 5, 1),
        ("engine.assemble", 47, 25, 1),      # the next batch, prefetched
        ("engine.h2d", 48, 15, 1),
        ("engine.stack", 64, 7, 1),
        ("engine.deliver", 82, 12, 1),
        ("engine.step", 200, 60, 1),
        ("engine.execute", 205, 35, 1),
        ("executor.call", 206, 5, 1),
        ("engine.deliver", 242, 10, 1),
    ])
    b = step_breakdown(data, "p")
    assert b["n_steps"] == 2
    assert b["step_s"] == pytest.approx(160e-6)
    assert b["assemble_s"] == pytest.approx(55e-6)
    assert b["execute_s"] == pytest.approx((40 - 25 + 35) * 1e-6)
    assert b["deliver_s"] == pytest.approx(22e-6)
    # 100 - 30 - 40 - 12 and 60 - 35 - 10
    assert b["step_self_s"] == pytest.approx(33e-6)
    parts = ("assemble_s", "execute_s", "deliver_s", "step_self_s")
    assert sum(b[k] for k in parts) == pytest.approx(b["step_s"])


@pytest.mark.parametrize("name,taps", [
    ("canny-m", 38), ("tbackground-t", 11), ("sift-dog", 299)])
def test_executor_call_carries_taps(global_trace, name, taps):
    """``executor.call`` of both executors names the window elements the
    pipeline's stage functions read per output pixel."""
    dag = {**algorithms.ALGORITHMS, **algorithms.VIDEO_ALGORITHMS}[name]()
    args = ({"in": np.zeros((2, 16, 128), np.float32)},)
    if dag.is_temporal():
        ex = make_video_executor(dag, 16, 128, rows_per_step=8, chunk=2)
        args += (ex.init_state(),)
    else:
        ex = make_executor(dag, 16, 128, batch=2, rows_per_step=8)
    assert ex.taps == taps
    # the span, not the kernel, is under test
    dataclasses.replace(ex, _fn=lambda *a: None)(*args)
    (call,) = [e for e in trace.events() if e.name == "executor.call"]
    assert call.attrs["taps"] == taps
