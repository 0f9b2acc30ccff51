"""Serving control plane: admission, deadlines, policies, outcomes —
units first, then the resilient engine paths end to end."""
import numpy as np
import pytest

from repro.imaging import FrameEngine, FrameRequest
from repro.kernels import ref
from repro.obs import trace
from repro.resilience import (AdmissionController, CancelledFrame,
                              CircuitBreaker, FailedFrame, FallbackLadder,
                              LadderExhausted, Priority, RejectedFrame,
                              ResilienceConfig, RetryPolicy, ShedFrame,
                              TokenBucket, pick_shed_victim, screen_frames,
                              split_expired)
from repro.resilience.chaos import ChaosMonkey, install_chaos
from repro.video import CompletedVideoFrame, VideoEngine, VideoFrame

RNG = np.random.RandomState(7)


def _frame(shape=(16, 24)):
    return RNG.rand(*shape).astype(np.float32)


def _req(rid, name="unsharp-m", shape=(16, 24), **kw):
    return FrameRequest(rid=rid, pipeline=name,
                        frames={"in": _frame(shape)}, **kw)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -------------------------------------------------------------- screening
def test_screen_frames_catalogue_of_defects():
    clean = {"in": _frame()}
    assert screen_frames(clean, {"in"}) is None
    assert screen_frames({}, {"in"})[0] == "missing_inputs"
    assert screen_frames({"in": _frame().astype(np.complex64)},
                         {"in"})[0] == "bad_dtype"
    assert screen_frames({"in": _frame().ravel()}, {"in"})[0] == "bad_shape"
    bad = _frame()
    bad[3, 4] = np.nan
    assert screen_frames({"in": bad}, {"in"})[0] == "nonfinite"
    bad = _frame()
    bad[0, 0] = np.inf
    assert screen_frames({"in": bad}, {"in"})[0] == "nonfinite"
    # two inputs disagreeing on shape
    assert screen_frames({"a": _frame((8, 8)), "b": _frame((4, 4))},
                         {"a", "b"})[0] == "bad_shape"
    # a stream-pinned shape is enforced
    assert screen_frames(clean, {"in"}, expect_shape=(8, 8))[0] \
        == "bad_shape"
    assert screen_frames(clean, {"in"}, expect_shape=(16, 24)) is None
    # integer frames are numeric enough (cast downstream)
    assert screen_frames({"in": np.zeros((4, 4), np.int32)}, {"in"}) is None


def test_token_bucket_burst_then_refill():
    clk = FakeClock()
    b = TokenBucket(rate=10.0, burst=3.0, clock=clk)
    assert [b.try_take() for _ in range(4)] == [True, True, True, False]
    clk.t += 0.1                      # 1 token refilled
    assert b.try_take()
    assert not b.try_take()
    clk.t += 10.0                     # refill clamps at burst
    assert b.tokens == pytest.approx(3.0)
    with pytest.raises(ValueError, match="rate/burst"):
        TokenBucket(rate=0.0, burst=1.0)


def test_admission_controller_per_key_isolation():
    clk = FakeClock()
    ac = AdmissionController(rate=1.0, burst=1.0, clock=clk)
    assert ac.allow("a") and not ac.allow("a")
    assert ac.allow("b")              # separate bucket
    ac.forget("a")
    assert ac.allow("a")              # fresh bucket starts full
    # rate=None disables limiting entirely
    unlimited = AdmissionController(rate=None)
    assert all(unlimited.allow("x") for _ in range(100))
    assert len(unlimited) == 0        # no bucket state accumulated


# ---------------------------------------------------------------- shedding
def test_pick_shed_victim_priority_then_deadline():
    items = [("lo", Priority.LOW, None, 1.0),
             ("hi", Priority.HIGH, None, 2.0)]

    def pick(new_priority, now=10.0, its=items):
        return pick_shed_victim(its, int(new_priority), now,
                                priority_of=lambda it: int(it[1]),
                                deadline_of=lambda it: it[2],
                                age_of=lambda it: it[3])

    # a NORMAL newcomer evicts the LOW resident, never the HIGH one
    assert pick(Priority.NORMAL)[0] == "lo"
    # a LOW newcomer finds nothing strictly worse: refused, no churn
    assert pick(Priority.LOW) is None
    # ... unless a resident is already past its deadline
    expired = [("late", Priority.NORMAL, 5.0, 1.0),
               ("ok", Priority.NORMAL, 50.0, 2.0)]
    assert pick(Priority.LOW, its=expired)[0] == "late"
    assert pick_shed_victim([], 0, 0.0, priority_of=int,
                            deadline_of=lambda _: None,
                            age_of=float) is None


def test_split_expired():
    items = [("a", 5.0), ("b", None), ("c", 20.0)]
    live, expired = split_expired(items, now=10.0,
                                  deadline_of=lambda it: it[1])
    assert [x[0] for x in live] == ["b", "c"]
    assert [x[0] for x in expired] == ["a"]


# ---------------------------------------------------------------- policies
def test_retry_policy_recovers_and_exhausts():
    calls = []
    retried = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    p = RetryPolicy(max_attempts=3, base_delay_s=0.001, seed=1)
    out = p.call(flaky, sleep=lambda _: None,
                 on_retry=lambda a, d, e: retried.append((a, d)))
    assert out == "ok" and len(calls) == 3 and len(retried) == 2
    assert all(d > 0 for _, d in retried)

    def always():
        raise ValueError("permanent")

    with pytest.raises(ValueError, match="permanent"):
        p.call(always, sleep=lambda _: None)


def test_retry_backoff_is_seeded_and_bounded():
    a = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.02,
                    multiplier=2.0, jitter=0.5, seed=42)
    b = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.02,
                    multiplier=2.0, jitter=0.5, seed=42)
    da = [a.backoff_s(k) for k in range(1, 5)]
    db = [b.backoff_s(k) for k in range(1, 5)]
    assert da == db                     # same seed, same schedule
    assert all(0.005 <= d <= 0.03 for d in da)   # jitter in [0.5x, 1.5x]
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=2.0)


def test_retry_attempt_timeout_regains_control():
    import threading
    wedged = threading.Event()

    def hang():
        wedged.wait(5.0)

    p = RetryPolicy(max_attempts=1, timeout_s=0.05)
    from repro.resilience import AttemptTimeout
    with pytest.raises(AttemptTimeout):
        p.call(hang)
    wedged.set()                      # release the abandoned thread


def test_circuit_breaker_lifecycle():
    clk = FakeClock()
    br = CircuitBreaker(failure_threshold=2, reset_after_s=1.0, clock=clk)
    assert br.allow()
    br.record_failure()
    assert br.state == br.CLOSED and br.allow()
    br.record_failure()               # second consecutive: trips
    assert br.state == br.OPEN and br.trips == 1
    assert not br.allow()
    clk.t += 1.0                      # reset window elapsed: one probe
    assert br.allow() and br.state == br.HALF_OPEN
    assert not br.allow()             # probe already in flight
    br.record_failure()               # probe failed: reopen immediately
    assert br.state == br.OPEN and br.trips == 2
    clk.t += 1.0
    assert br.allow()
    br.record_success()               # probe succeeded: fully closed
    assert br.state == br.CLOSED and br.failures == 0
    assert br.allow()


def test_fallback_ladder_descends_and_reports():
    clk = FakeClock()
    fell = []
    lad = FallbackLadder(retry=RetryPolicy(max_attempts=1),
                         failure_threshold=1, reset_after_s=10.0,
                         clock=clk, sleep=lambda _: None,
                         on_fallback=lambda k, r, e: fell.append(r))

    def boom():
        raise RuntimeError("tuned broken")

    out, rung = lad.run("p", [("tuned", boom), ("default", lambda: 42)])
    assert (out, rung) == (42, "default") and fell == ["tuned"]
    # the failed rung's breaker is now open: skipped without calling
    out, rung = lad.run("p", [("tuned", boom), ("default", lambda: 7)])
    assert rung == "default"
    # every rung gone -> LadderExhausted carrying per-rung evidence
    with pytest.raises(LadderExhausted) as ei:
        lad.run("q", [("default", boom)])
    assert ei.value.key == "q"
    assert [r for r, _ in ei.value.errors] == ["default"]
    # per-(key, rung) isolation: key "q" tripping never affects key "p"
    assert lad.breaker("p", "default").state == CircuitBreaker.CLOSED


# --------------------------------------------------- resilient FrameEngine
def _cfg(**kw):
    kw.setdefault("retry",
                  RetryPolicy(max_attempts=2, base_delay_s=1e-4, seed=0))
    return ResilienceConfig(**kw)


def test_resilient_submit_quarantines_instead_of_raising():
    eng = FrameEngine(max_batch=2, max_pending=8, resilience=_cfg())
    bad = [
        FrameRequest(rid=0, pipeline="no-such", frames={"in": _frame()}),
        FrameRequest(rid=1, pipeline="tmotion-t", frames={"in": _frame()}),
        FrameRequest(rid=2, pipeline="unsharp-m", frames={}),
        FrameRequest(rid=3, pipeline="unsharp-m",
                     frames={"in": _frame().ravel()}),
    ]
    reasons = [eng.submit(r) for r in bad]
    assert all(isinstance(r, RejectedFrame) and not r for r in reasons)
    assert [r.reason for r in reasons] == [
        "unknown_pipeline", "temporal_pipeline", "missing_inputs",
        "bad_shape"]
    assert not any(r.retryable for r in reasons)   # permanent defects
    nan = _frame()
    nan[1, 1] = np.nan
    rej = eng.submit(FrameRequest(rid=4, pipeline="unsharp-m",
                                  frames={"in": nan}))
    assert rej.reason == "nonfinite"
    # engine still healthy and the books balance: 5 offered, 5 rejected
    assert eng.submit(_req(5)) is True
    out = eng.step()
    assert len(out) == 1 and out[0].rid == 5
    rec = eng.metrics.reconcile()
    assert rec["balanced"] and rec["offered"] == 6 and rec["rejected"] == 5


def _frame_engine(cfg, **kw):
    """A resilient FrameEngine and a way to offer it request ``rid``."""
    eng = FrameEngine(resilience=cfg, **kw)
    return eng, lambda rid, **r: eng.submit(_req(rid, **r))


def _video_engine(cfg, max_batch=None, **kw):
    """A resilient VideoEngine with one open stream, offered the same
    way; a step serves up to ``chunk`` frames, not ``max_batch``."""
    eng = VideoEngine(resilience=cfg, **kw)
    sid = eng.open_stream("tmotion-t", 16, 24)
    return eng, lambda rid, **r: eng.submit(
        VideoFrame(sid, {"in": _frame()}, rid=rid, **r))


ENGINES = pytest.mark.parametrize("make", [_frame_engine, _video_engine],
                                  ids=["frame", "video"])


@ENGINES
def test_resilient_rate_limit_is_retryable(make):
    eng, submit = make(_cfg(rate=1000.0, burst=2.0), max_pending=64)
    verdicts = [submit(i) for i in range(4)]
    assert verdicts[:2] == [True, True]
    rejected = [v for v in verdicts if isinstance(v, RejectedFrame)]
    assert rejected and all(v.reason == "rate_limited" and v.retryable
                            for v in rejected)


# what the first resident, the second and a newcomer ask: FrameEngine
# evicts the lowest priority class; within one stream every frame shares
# the session's priority, so VideoEngine evicts only an expired resident
@pytest.mark.parametrize("make,asks", [
    (_frame_engine, [dict(priority=Priority.LOW),
                     dict(priority=Priority.HIGH),
                     dict(priority=Priority.NORMAL)]),
    (_video_engine, [dict(deadline_s=-1.0), {}, {}]),
], ids=["frame", "video"])
def test_overload_sheds_lowest_priority_first(make, asks):
    eng, submit = make(_cfg(), max_batch=2, max_pending=2)
    assert submit(0, **asks[0]) is True
    assert submit(1, **asks[1]) is True
    # queue full; the newcomer displaces resident 0
    assert submit(2, **asks[2]) is True
    # ...but not an in-SLA resident of its own class
    refused = submit(3, **asks[2])
    assert refused.reason == "saturated" and refused.retryable
    outcomes = []
    while eng.pending or not outcomes:
        outcomes += eng.step()
    shed = [o for o in outcomes if isinstance(o, ShedFrame)]
    assert [s.rid for s in shed] == [0]
    assert shed[0].reason == "overload"
    done = {o.rid for o in outcomes if not isinstance(o, ShedFrame)}
    assert done == {1, 2}
    assert eng.metrics.reconcile()["balanced"]


@ENGINES
def test_expired_deadlines_swept_before_execution(make):
    eng, submit = make(_cfg())
    assert submit(0, deadline_s=-1.0) is True            # born expired
    assert submit(1) is True
    outcomes = []
    while eng.pending or not outcomes:
        outcomes += eng.step()
    shed = [o for o in outcomes if isinstance(o, ShedFrame)]
    assert len(shed) == 1 and shed[0].rid == 0
    assert shed[0].reason == "deadline" and shed[0].overdue_s > 0
    assert {o.rid for o in outcomes} - {0} == {1}
    assert eng.metrics.frames_shed == 1


def test_fallback_ladder_serves_via_reference_when_compiles_fail():
    eng = FrameEngine(max_batch=2, resilience=_cfg(breaker_failures=1))
    monkey = ChaosMonkey(seed=0, compile=1.0)   # every compile fails
    install_chaos(eng.cache, monkey)
    reqs = [_req(i) for i in range(2)]
    for r in reqs:
        assert eng.submit(r) is True
    outcomes = eng.step()
    assert len(outcomes) == 2
    dag = eng.cache.dag_for("unsharp-m")
    for r, c in zip(reqs, outcomes):
        assert c.rung == "reference"
        want = np.asarray(ref.stencil_pipeline_ref(dag, r.frames))
        np.testing.assert_allclose(np.asarray(c.output), want,
                                   rtol=0, atol=0)
    assert eng.metrics.fallback_frames == 2
    assert eng.metrics.executor_retries >= 1
    assert eng.metrics.reconcile()["balanced"]


def test_executor_exception_becomes_failed_frames_strict_mode():
    """Satellite regression: an executor blowing up mid-step must not
    strand the popped batch or poison the engine — in *legacy* mode too."""
    eng = FrameEngine(max_batch=2)                 # resilience=None
    monkey = ChaosMonkey(seed=0, executor=1.0)     # every call raises
    install_chaos(eng.cache, monkey)
    for i in range(2):
        assert eng.submit(_req(i))
    outcomes = eng.step()
    assert len(outcomes) == 2
    assert all(isinstance(o, FailedFrame) for o in outcomes)
    assert {o.rid for o in outcomes} == {0, 1}
    assert all("InjectedFault" in o.error for o in outcomes)
    assert eng.metrics.frames_failed == 2
    assert eng.pending == 0                        # nothing stranded
    # chaos off: the same engine serves the next request normally
    monkey.rates["executor"] = 0.0
    assert eng.submit(_req(9))
    ok = eng.step()
    assert len(ok) == 1 and ok[0].rid == 9
    assert eng.metrics.reconcile()["balanced"]


def test_run_returns_structured_outcomes_for_lost_rids():
    eng = FrameEngine(resilience=_cfg())
    nan = _frame()
    nan[0, 0] = np.nan
    reqs = [_req(0),
            FrameRequest(rid=1, pipeline="unsharp-m", frames={"in": nan}),
            _req(2)]
    results = eng.run(reqs)
    assert set(results) == {0, 1, 2}
    assert isinstance(results[1], RejectedFrame)
    assert results[1].reason == "nonfinite"
    dag = eng.cache.dag_for("unsharp-m")
    for rid in (0, 2):
        want = np.asarray(ref.stencil_pipeline_ref(dag, reqs[rid].frames))
        got = np.asarray(results[rid])
        tol = 3 * np.spacing(np.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=0, atol=tol)


# --------------------------------------------------- resilient VideoEngine
def test_close_stream_refuses_then_cancels_in_flight_frames():
    """Satellite regression: closing a stream must never silently race
    its queued frames — refuse by default, drain as CancelledFrame on
    request, and keep the books exact either way."""
    eng = VideoEngine(chunk=2)
    sid = eng.open_stream("tmotion-t", 8, 8)
    for i in range(3):
        assert eng.submit(VideoFrame(sid, {"in": _frame((8, 8))}, rid=i))
    with pytest.raises(ValueError, match="undelivered"):
        eng.close_stream(sid)
    assert sid in eng._sessions                    # refusal left it open
    cancelled = eng.close_stream(sid, cancel=True)
    assert [c.rid for c in cancelled] == [0, 1, 2]
    assert all(isinstance(c, CancelledFrame)
               and c.reason == "stream_closed" for c in cancelled)
    assert eng.metrics.frames_cancelled == 3
    assert eng.pending == 0
    rec = eng.metrics.reconcile()
    assert rec["balanced"] and rec["in_flight"] == 0


def test_video_resilient_rejects_unknown_stream_and_bad_shape():
    eng = VideoEngine(resilience=_cfg())
    rej = eng.submit(VideoFrame(999, {"in": _frame((8, 8))}))
    assert isinstance(rej, RejectedFrame) and rej.reason == "unknown_stream"
    sid = eng.open_stream("tmotion-t", 8, 8)
    rej = eng.submit(VideoFrame(sid, {"in": _frame((4, 4))}))
    assert rej.reason == "bad_shape"
    assert eng.submit(VideoFrame(sid, {"in": _frame((8, 8))})) is True
    assert eng.metrics.reconcile()["balanced"]


def test_video_executor_exception_structured_in_strict_mode():
    eng = VideoEngine(chunk=2)                     # resilience=None
    monkey = ChaosMonkey(seed=0, executor=1.0)
    install_chaos(eng.cache, monkey)
    sid = eng.open_stream("tmotion-t", 8, 8)
    for i in range(2):
        assert eng.submit(VideoFrame(sid, {"in": _frame((8, 8))}, rid=i))
    outcomes = eng.step()
    failed = [o for o in outcomes if isinstance(o, FailedFrame)]
    assert [f.rid for f in failed] == [0, 1]
    assert eng.pending == 0
    monkey.rates["executor"] = 0.0
    assert eng.submit(VideoFrame(sid, {"in": _frame((8, 8))}, rid=2))
    served = eng.step()
    assert len(served) == 1 and served[0].rid == 2
    assert served[0].index == 0       # stream position: failures never ran
    assert eng.metrics.reconcile()["balanced"]


def test_video_reference_fallback_resumes_compiled_stream():
    """The stateful-fallback contract: frames served off the reference
    rung mid-stream must match the full-stream oracle, and the compiled
    path must resume from the oracle-rebuilt rings afterwards."""
    from repro.core.algorithms import execute_reference_video

    eng = VideoEngine(chunk=1, resilience=_cfg(breaker_failures=1,
                                               breaker_reset_s=0.0))
    monkey = ChaosMonkey(seed=0)
    install_chaos(eng.cache, monkey)
    sid = eng.open_stream("tmotion-t", 8, 8)
    frames = [_frame((8, 8)) for _ in range(6)]
    outs, rungs = [], []
    for t, fr in enumerate(frames):
        if t == 2:       # blackout: compiled rungs broken for frames 2-3
            monkey.rates["compile"] = 1.0
            eng.cache.evict_executors()
        elif t == 4:     # recovery (breaker_reset_s=0 reopens instantly)
            monkey.rates["compile"] = 0.0
        assert eng.submit(VideoFrame(sid, {"in": fr}, rid=t)) is True
        got = eng.step()
        comp = [c for c in got if isinstance(c, CompletedVideoFrame)]
        assert [c.rid for c in comp] == [t]
        outs.append(np.asarray(comp[0].output))
        rungs.append(comp[0].rung)
    assert rungs[2] == rungs[3] == "reference"
    assert rungs[0] == rungs[1] == "default"
    assert rungs[4] == rungs[5] == "default"       # resumed compiled
    dag = eng.cache.dag_for("tmotion-t")
    want = np.asarray(execute_reference_video(
        dag, {"in": np.stack(frames)}))
    got = np.stack(outs)
    tol = 32 * np.spacing(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    assert eng.metrics.fallback_frames == 2
    assert eng.metrics.reconcile()["balanced"]


def test_resilience_config_defaults_are_strictly_additive():
    """Default-constructed config must not rate-limit or deadline
    anything — only the structured-outcome behavior changes."""
    cfg = ResilienceConfig()
    assert cfg.rate is None and cfg.default_deadline_s is None
    assert cfg.shed_on_overload and cfg.shed_expired
    assert cfg.reference_fallback
    eng = FrameEngine(resilience=cfg)
    for i in range(4):
        assert eng.submit(_req(i)) is True
    outcomes = []
    while eng.pending:
        outcomes += eng.step()
    assert sorted(o.rid for o in outcomes) == [0, 1, 2, 3]
    assert all(not o.deadline_missed for o in outcomes)
