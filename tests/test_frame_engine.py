"""FrameEngine: ordering, batching, backpressure under bursty load."""
import numpy as np
import pytest

from repro.imaging import FrameEngine, FrameRequest, PlanCache
from repro.kernels import ref

RNG = np.random.RandomState(13)


def _req(rid, name, shape=(24, 32)):
    return FrameRequest(rid=rid, pipeline=name,
                        frames={"in": RNG.rand(*shape).astype(np.float32)})


def test_submit_rejects_malformed_requests_at_admission():
    """Bad requests must raise at submit(), never poison a batch."""
    eng = FrameEngine(max_batch=2, max_pending=8)
    with pytest.raises(KeyError):
        eng.submit(FrameRequest(rid=0, pipeline="no-such",
                                frames={"in": np.zeros((8, 8), np.float32)}))
    with pytest.raises(ValueError, match="needs inputs"):
        eng.submit(FrameRequest(rid=1, pipeline="canny-m",
                                frames={"img": np.zeros((8, 8), np.float32)}))
    with pytest.raises(ValueError, match="share"):
        eng.submit(FrameRequest(
            rid=2, pipeline="canny-m",
            frames={"in": np.zeros((8, 8), np.float32),
                    "extra": np.zeros((4, 4), np.float32)}))
    assert eng.submit(_req(3, "canny-m"))       # engine still healthy
    assert len(eng.step()) == 1


def test_submit_backpressure():
    eng = FrameEngine(max_batch=2, max_pending=3)
    assert all(eng.submit(_req(i, "harris-s")) for i in range(3))
    assert not eng.submit(_req(3, "harris-s"))      # queue full: refused
    assert eng.metrics.frames_rejected == 1
    assert len(eng.step()) == 2                     # drain one batch...
    assert eng.submit(_req(3, "harris-s"))          # ...now admitted


def test_per_pipeline_fifo_ordering():
    eng = FrameEngine(max_batch=3, max_pending=32)
    order = {"canny-s": [], "unsharp-m": []}
    reqs = [_req(i, ["canny-s", "unsharp-m"][i % 2]) for i in range(12)]
    for r in reqs:
        assert eng.submit(r)
    while eng.pending:
        for c in eng.step():
            order[c.pipeline].append(c.rid)
    assert order["canny-s"] == [0, 2, 4, 6, 8, 10]
    assert order["unsharp-m"] == [1, 3, 5, 7, 9, 11]


def test_mixed_shapes_never_share_a_batch():
    eng = FrameEngine(max_batch=4, max_pending=32)
    shapes = [(24, 32), (24, 32), (16, 24), (16, 24), (24, 32)]
    for i, s in enumerate(shapes):
        assert eng.submit(_req(i, "harris-m", shape=s))
    done = []
    while eng.pending:
        batch = eng.step()
        assert len({tuple(c.output.shape) for c in batch}) == 1
        done += batch
    assert sorted(c.rid for c in done) == list(range(5))
    # (24,32) head batches rids 0,1 then stops at the (16,24) shape change
    assert [c.rid for c in done[:2]] == [0, 1]


def test_bursty_load_completes_all_and_outputs_match_reference():
    """More requests than queue capacity, mixed pipelines and sizes:
    everything completes exactly once, every output matches the oracle,
    and backpressure fired along the way."""
    eng = FrameEngine(max_batch=3, max_pending=4, tile_shape=(40, 48))
    reqs = [FrameRequest(
        rid=i, pipeline=["canny-m", "unsharp-m", "harris-s"][i % 3],
        frames={"in": RNG.rand(*((50, 70) if i % 5 == 0 else (24, 32))
                               ).astype(np.float32)})
        for i in range(14)]
    res = eng.run(reqs)
    assert sorted(res) == list(range(14))
    assert eng.metrics.frames_completed == 14
    assert eng.metrics.frames_rejected > 0          # the burst overflowed
    assert eng.metrics.latency_s.count == 14
    assert eng.metrics.vmem_high_water > 0
    for r in reqs:
        exp = ref.stencil_pipeline_ref(eng.cache.dag_for(r.pipeline),
                                       dict(r.frames))
        np.testing.assert_allclose(np.asarray(res[r.rid]), np.asarray(exp),
                                   rtol=1e-4, atol=1e-5)


def test_partial_batch_zero_slots_do_not_leak():
    """One live request in a 4-slot batch: idle zero-filled slots must not
    perturb the live frame (the frame-boundary masking argument)."""
    eng = FrameEngine(max_batch=4, max_pending=8)
    solo = _req(0, "canny-m")
    assert eng.submit(solo)
    (c,) = eng.step()
    exp = ref.stencil_pipeline_ref(eng.cache.dag_for("canny-m"),
                                   dict(solo.frames))
    np.testing.assert_allclose(np.asarray(c.output), np.asarray(exp),
                               rtol=1e-4, atol=1e-5)


def test_engine_metrics_snapshot_shape():
    eng = FrameEngine(max_batch=2, max_pending=8)
    eng.run([_req(i, "unsharp-m") for i in range(4)])
    snap = eng.metrics.snapshot()
    assert snap["frames_completed"] == 4
    assert snap["batches"] == 2
    assert snap["mean_batch_fill"] == pytest.approx(1.0)
    assert snap["per_pipeline"] == {"unsharp-m": 4}
    assert snap["fps_execute"] > 0


# -- the next full batch is copied and stacked while the current one runs

def test_peek_batch_leaves_in_place_what_assemble_batch_pops():
    from repro.imaging.serving import BoundedFifo, assemble_batch, peek_batch
    queues = {k: BoundedFifo(8) for k in "ab"}
    for age, key, shape in [(1, "b", 1), (2, "a", 1), (3, "b", 1),
                            (4, "b", 2), (5, "b", 2)]:
        queues[key].push((age, shape))
    kw = dict(age_of=lambda it: it[0],
              compatible=lambda x, y: x[1] == y[1])
    peeked = peek_batch(queues, 4, **kw)
    assert peeked == ("b", [(1, 1), (3, 1)])    # stops at the shape change
    assert [len(q) for q in queues.values()] == [1, 4]
    assert assemble_batch(queues, 4, **kw) == peeked
    assert [len(q) for q in queues.values()] == [1, 2]
    assert peek_batch({}, 4, **kw) == (None, [])


def _queued(n, start=0, pipeline="unsharp-m"):
    return [_req(start + i, pipeline) for i in range(n)]


def _one_batch_at_a_time(reqs, max_batch):
    """The path without prefetch: each step finds only its own batch
    queued."""
    eng = FrameEngine(max_batch=max_batch, max_pending=16)
    out = {}
    for i in range(0, len(reqs), max_batch):
        for r in reqs[i:i + max_batch]:
            assert eng.submit(r)
        out.update((c.rid, c.output) for c in eng.step())
        assert eng.pending == 0
    assert eng.metrics.batches_prefetched == 0
    return out


def test_prefetch_serves_each_batch_on_its_own_step_bitwise():
    mb = 2
    reqs = _queued(3 * mb)
    eng = FrameEngine(max_batch=mb, max_pending=16)
    for r in reqs:
        assert eng.submit(r)
    steps = []
    while eng.pending:
        steps.append(eng.step())
        # the prepared batch counts as pending until its step serves it
        assert eng.pending == len(reqs) - mb * len(steps)
    assert [[c.rid for c in s] for s in steps] == [[0, 1], [2, 3], [4, 5]]
    assert eng.metrics.batches_prefetched == 2
    assert eng.metrics.batches == 3
    assert eng.metrics.snapshot()["batches_prefetched"] == 2
    alone = _one_batch_at_a_time(reqs, mb)
    for c in (c for s in steps for c in s):
        np.testing.assert_array_equal(np.asarray(c.output),
                                      np.asarray(alone[c.rid]))


@pytest.mark.parametrize("queued", [1, 2, 3], ids=["one", "full", "partial"])
def test_no_prefetch_without_a_full_batch_behind(queued):
    """One frame, one full batch, or a full batch with a partial one
    behind it: no step takes work early."""
    mb = 2
    eng = FrameEngine(max_batch=mb, max_pending=16)
    for r in _queued(queued):
        assert eng.submit(r)
    first = eng.step()
    assert [c.rid for c in first] == list(range(min(queued, mb)))
    assert eng.metrics.batches_prefetched == 0
    assert eng.pending == queued - len(first)


def test_prefetched_copy_fault_fails_only_its_batch_on_the_next_step():
    mb = 2
    reqs = _queued(3 * mb)
    # a frame the host -> device copy refuses, in the second batch
    reqs[3].frames = {"in": np.full((24, 32), "x", dtype=object)}
    eng = FrameEngine(max_batch=mb, max_pending=16)
    for r in reqs:
        assert eng.submit(r)
    first = eng.step()
    assert [(type(c).__name__, c.rid) for c in first] == \
        [("CompletedFrame", 0), ("CompletedFrame", 1)]
    assert eng.metrics.batches_prefetched == 1 and eng.pending == 4
    second = eng.step()
    assert [(type(c).__name__, c.rid) for c in second] == \
        [("FailedFrame", 2), ("FailedFrame", 3)]
    third = eng.step()
    assert [(type(c).__name__, c.rid) for c in third] == \
        [("CompletedFrame", 4), ("CompletedFrame", 5)]
    assert eng.metrics.frames_failed == 2
    assert eng.metrics.frames_completed == 4 and eng.pending == 0


def test_resilient_mode_never_prefetches():
    from repro.resilience import ResilienceConfig
    mb = 2
    eng = FrameEngine(max_batch=mb, max_pending=16,
                      resilience=ResilienceConfig())
    for r in _queued(3 * mb):
        assert eng.submit(r) is True
    served = []
    while eng.pending:
        served.append([c.rid for c in eng.step()])
        assert eng.pending == 3 * mb - mb * len(served)
    assert served == [[0, 1], [2, 3], [4, 5]]
    assert eng.metrics.batches_prefetched == 0
