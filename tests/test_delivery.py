"""Output delivery: both engines hand out a batch's or chunk's frames
through one compiled split (``unstack``), not one slice per frame. Each
delivered output is its own device array, a bitwise copy of its row."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.imaging import FrameEngine, FrameRequest
from repro.kernels import ref
from repro.kernels.stencil_pipeline import unstack
from repro.obs import trace
from repro.video import VideoEngine, VideoFrame

H, W = 24, 32


@pytest.fixture
def global_trace():
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.clear()


def _frames(n, seed=0, shape=(H, W)):
    rng = np.random.RandomState(seed)
    return [rng.rand(*shape).astype(np.float32) for _ in range(n)]


def _batch(b, seed=0):
    return jnp.asarray(np.stack(_frames(b, seed)))


def _deliveries():
    return [(e.attrs["frames"], e.attrs["programs"])
            for e in trace.events() if e.name == "engine.deliver"]


@pytest.mark.parametrize("b", [1, 3, 4])
def test_unstack_is_bitwise_each_row(b):
    x = _batch(b)
    outs = unstack(x)
    assert len(outs) == b
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(x[i]))


@pytest.mark.parametrize("b", [1, 4])
def test_unstack_returns_distinct_frame_arrays(b):
    x = _batch(b)
    outs = unstack(x)
    assert all(o.shape == (H, W) and o.dtype == x.dtype for o in outs)
    ptrs = {o.unsafe_buffer_pointer() for o in outs}
    assert len(ptrs) == b and x.unsafe_buffer_pointer() not in ptrs


def test_unstack_outputs_survive_the_batch():
    x = _batch(4, seed=3)
    want = np.asarray(x).copy()
    outs = unstack(x)
    x.delete()
    assert x.is_deleted()
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), want[i])


def test_unstack_program_is_named():
    text = unstack.lower(_batch(2)).as_text()
    assert text.splitlines()[0].startswith("module @jit_imagen_unstack ")


@pytest.mark.parametrize("n", [4, 3, 1], ids=["full", "partial", "one"])
def test_frame_engine_delivers_one_split_per_batch(global_trace, n):
    eng = FrameEngine(max_batch=4, max_pending=8)
    frames = _frames(n, seed=n)
    done = eng.run([FrameRequest(rid=i, pipeline="unsharp-m",
                                 frames={"in": f})
                    for i, f in enumerate(frames)])
    assert sorted(done) == list(range(n))
    dag = eng.cache.dag_for("unsharp-m")
    ex = eng.cache.executor_for("unsharp-m", H, W, batch=4)
    padded = np.stack(frames + [np.zeros((H, W), np.float32)] * (4 - n))
    batch_out = np.asarray(ex({"in": jnp.asarray(padded)}))
    for i, f in enumerate(frames):
        got = np.asarray(done[i])
        assert got.shape == (H, W)
        np.testing.assert_array_equal(got, batch_out[i])
        np.testing.assert_allclose(
            got, np.asarray(ref.stencil_pipeline_ref(dag, {"in": f})),
            rtol=1e-4, atol=1e-5)
    assert _deliveries() == [(n, 1)]


def test_video_engine_delivers_one_split_per_chunk(global_trace):
    eng = VideoEngine(chunk=4)
    sid = eng.open_stream("tbackground-t", H, W)
    frames = _frames(8, seed=5)
    outs = []
    for f in frames:
        assert eng.submit(VideoFrame(sid, {"in": f})) is True
    while eng.pending:
        outs += eng.step()
    assert [c.index for c in outs] == list(range(8))
    got = np.stack([np.asarray(c.output) for c in outs])
    exp = np.asarray(ref.video_pipeline_ref(
        eng.cache.dag_for("tbackground-t"), {"in": np.stack(frames)}))
    if not (got == exp).all():
        np.testing.assert_allclose(
            got, exp, rtol=0, atol=32 * np.spacing(np.abs(exp).max()))
    assert len({c.output.unsafe_buffer_pointer() for c in outs}) == 8
    assert _deliveries() == [(4, 1), (4, 1)]


def test_split_compiles_once_per_frame_shape_not_per_fill():
    """Batches of fill 1 and 4 at one frame shape share one split
    program: the padded batch is split whole, whatever its fill."""
    shape = (40, 48)         # a frame shape no other test here serves
    eng = FrameEngine(max_batch=4, max_pending=8)
    before = unstack._cache_size()
    for n, seed in ((1, 11), (4, 12), (2, 13)):
        frames = _frames(n, seed=seed, shape=shape)
        done = eng.run([FrameRequest(rid=i, pipeline="unsharp-m",
                                     frames={"in": f})
                        for i, f in enumerate(frames)])
        assert len(done) == n
        assert unstack._cache_size() == before + 1
