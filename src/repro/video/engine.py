"""VideoEngine: multiplexed streaming of temporal pipelines.

VideoEngine runs on the serving core it shares with imaging.FrameEngine
(imaging/serving.py: admission, resilience, batch copy and delivery) —
but where the frame engine treats every request as independent, a video
stream is *stateful*: each temporal producer's last d-1 frames live in a
device-resident frame ring that must follow the stream, frame order
matters, and two streams of the same pipeline must never see each
other's history. The engine therefore
splits the world in two:

  * **compiled artifacts are shared** — one VideoExecutor per (pipeline,
    shape, chunk, row group) in the PlanCache, stateless across streams
    (history is an explicit argument/result, see kernels.VideoExecutor);
  * **state is per-session** — a VideoSession owns its frame rings, its
    FIFO of pending frames (bounded: a full queue refuses, backpressure
    to the caller), its delivery counter (outputs are emitted in
    submission order), and its warm-up accounting.

Warm-up semantics: a fresh session's frame rings are zeros, so the first
``warmup_frames`` outputs (the DAG's cumulative temporal extent) are
computed against zero history — valid, deterministic, bitwise equal to
the multi-frame reference, but flagged ``warm=False`` so a caller who
wants only fully-warmed output can drop them.

``step()`` serves the session whose head frame waited longest, advancing
up to ``chunk`` frames in one executor call when the pipeline's temporal
taps are input-only (the common case; see make_video_executor), falling
back to frame-at-a-time for pipelines with internal temporal producers.

**Resilient mode** (``resilience=ResilienceConfig(...)``) adds the
serving control plane: malformed/unknown-stream frames come back as
structured :class:`~repro.resilience.RejectedFrame` results instead of
raising, per-stream token buckets rate-limit admission, saturated
queues shed the most-expired resident, deadlines sweep expired work,
and execution descends a fallback ladder (tuned → default → pure-jnp
reference). The reference rung is the interesting one for a *stateful*
engine: each session keeps a host-side window of its last
``warmup_frames`` raw input frames, so the oracle can recompute the
stream's tail and hand back both the outputs and a rebuilt frame-ring
state — the device resumes the stream exactly where the oracle left it.
Dropped (shed) frames simply never happened to the stream: rings and
history advance only on served frames, which is precisely live-video
frame-dropping semantics.

In both modes an executor exception can no longer strand queued work:
frames that reached the executor but could not be served are delivered
as structured :class:`FailedFrame` results and the session state is
left at the last successfully served frame.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Mapping

import jax.numpy as jnp
import numpy as np

from repro.core.algorithms import execute_reference_video
from repro.imaging.plan_cache import PlanCache
from repro.imaging.serving import (BoundedFifo, Lane, ServingCore,
                                   assemble_batch, copy_and_stack,
                                   copy_frame, deliver)
from repro.imaging.tiling import rows_per_step_for_tile
from repro.kernels.stencil_pipeline import init_frame_state
from repro.obs import trace
from repro.resilience import (CancelledFrame, FailedFrame, Priority,
                              RejectedFrame, ResilienceConfig, screen_frames)


@dataclasses.dataclass
class VideoFrame:
    """One submitted frame of one stream (inputs keyed by stage name)."""
    stream: int
    frames: Mapping[str, np.ndarray]
    submitted_at: float = 0.0             # stamped by the engine
    priority: int = Priority.NORMAL       # stamped from the session
    deadline_s: float | None = None       # relative SLA; None = config's
    deadline: float | None = None         # absolute (obs clock), stamped
    rid: int | None = None                # optional client tag, echoed in
                                          # every outcome for accounting


@dataclasses.dataclass
class CompletedVideoFrame:
    stream: int
    pipeline: str
    index: int                            # position in the stream, from 0
    output: jnp.ndarray
    warm: bool                            # False while zero history shows
    latency_s: float
    rung: str = "default"                 # ladder rung that served it
    deadline_missed: bool = False
    rid: int | None = None                # echo of VideoFrame.rid


@dataclasses.dataclass
class VideoSession:
    """Per-stream serving state: the part that must NOT be shared."""
    sid: int
    pipeline: str
    h: int
    w: int
    state: dict[str, jnp.ndarray]         # frame rings {producer: (d-1,h,w)}
    queue: BoundedFifo
    warmup_frames: int
    inputs: frozenset                     # required input-stage names
    priority: int = Priority.NORMAL
    # resilient mode, temporal DAGs only: last ``warmup_frames`` raw
    # input frames (oldest -> newest) per input stage — the window the
    # reference fallback rung replays to serve off the compiled path
    history: dict[str, deque] | None = None
    submitted: int = 0
    delivered: int = 0
    opened_at: float = dataclasses.field(
        default_factory=time.perf_counter)
    first_warm_at: float | None = None


class VideoEngine(ServingCore):
    kind = "video"

    def __init__(self, cache: PlanCache | None = None,
                 chunk: int = 4, max_pending: int = 64,
                 rows_per_step: int = 8,
                 prefetch_depth: int = 1,
                 autotune: bool = False,
                 registry=None,
                 resilience: ResilienceConfig | None = None):
        super().__init__(cache, max_pending, rows_per_step, prefetch_depth,
                         autotune, registry, resilience)
        self.chunk = chunk
        self._sessions: dict[int, VideoSession] = {}
        self._ids = itertools.count()
        self.warmup_latency_s = self.metrics.registry.histogram(
            "video_engine_warmup_latency_s",
            help="stream open -> first fully-warm output, seconds")

    # ------------------------------------------------------------- streams
    def open_stream(self, pipeline: str, h: int, w: int,
                    priority: int = Priority.NORMAL) -> int:
        """Create a session: zeroed frame rings, empty queue. Executors
        compile lazily on the first step — opening a stream costs only
        the zero-state allocation."""
        dag = self.cache.dag_for(pipeline)
        sid = next(self._ids)
        warmup = dag.cumulative_extent(temporal=True)[0]
        history = None
        if self.resilience is not None and dag.is_temporal():
            history = {name: deque(maxlen=warmup)
                       for name in dag.input_stages()}
        self._sessions[sid] = VideoSession(
            sid=sid, pipeline=pipeline, h=h, w=w,
            state=init_frame_state(dag.temporal_depths(), h, w),
            queue=BoundedFifo(self.max_pending),
            warmup_frames=warmup,
            inputs=frozenset(dag.input_stages()),
            priority=int(priority), history=history)
        return sid

    def close_stream(self, sid: int,
                     cancel: bool = False) -> list[CancelledFrame]:
        """Tear down a session. A queue with undelivered frames refuses
        (raises) by default — closing must not silently race in-flight
        work. ``cancel=True`` drains those frames as structured
        :class:`CancelledFrame` results instead, keeping the
        reconciliation identity exact (they count as cancelled, not
        lost)."""
        s = self._sessions[sid]
        cancelled: list[CancelledFrame] = []
        if s.queue:
            if not cancel:
                raise ValueError(f"stream {sid} closed with {len(s.queue)} "
                                 f"undelivered frames")
            dropped = s.queue.drain()
            self.metrics.frames_cancelled += len(dropped)
            cancelled = [CancelledFrame(pipeline=s.pipeline, stream=sid,
                                        rid=f.rid)
                         for f in dropped]
            with trace.span("resilience.cancel", engine="video",
                            stream=sid, pipeline=s.pipeline,
                            n_frames=len(dropped)):
                pass
        if self._admission is not None:
            self._admission.forget(sid)
        del self._sessions[sid]
        return cancelled

    # ----------------------------------------------------------- admission
    def submit(self, frame: VideoFrame) -> bool | RejectedFrame:
        """Enqueue one frame; False = stream saturated (backpressure).
        Legacy strict mode raises on malformed frames here, at
        admission; resilient mode returns a falsy RejectedFrame for
        every refusal instead."""
        s = self._sessions.get(frame.stream)
        if self.resilience is not None:
            ok = self._submit_resilient(frame)
        else:
            if s is None:
                raise KeyError(f"unknown stream {frame.stream}")
            if not s.inputs <= set(frame.frames):
                raise ValueError(f"stream {s.sid}: pipeline {s.pipeline!r} "
                                 f"needs inputs {sorted(s.inputs)}, got "
                                 f"{sorted(frame.frames)}")
            for n in s.inputs:
                if tuple(np.shape(frame.frames[n])) != (s.h, s.w):
                    raise ValueError(
                        f"stream {s.sid}: frame shape "
                        f"{tuple(np.shape(frame.frames[n]))} != "
                        f"({s.h}, {s.w})")
            ok = self._push(frame, s.queue)
        if ok is True:
            s.submitted += 1
        return ok

    def _route(self, frame: VideoFrame) -> Lane | RejectedFrame:
        s = self._sessions.get(frame.stream)
        if s is None:
            return RejectedFrame("unknown_stream", stream=frame.stream,
                                 detail=f"no open stream {frame.stream}")
        defect = screen_frames(frame.frames, s.inputs,
                               expect_shape=(s.h, s.w))
        if defect is not None:
            reason, detail = defect
            return RejectedFrame(reason, pipeline=s.pipeline, detail=detail,
                                 stream=s.sid)
        frame.priority = int(s.priority)
        return Lane(s.queue, s.pipeline, s.sid)

    def _lanes(self):
        return (Lane(s.queue, s.pipeline, s.sid)
                for s in self._sessions.values())

    # ------------------------------------------------------------ execution
    def _run_chunk(self, s: VideoSession, frames: list[VideoFrame],
                   n: int, rps: int, tune: bool):
        """Full-chunk executor call. Returns (outs, new_state, vmem);
        crucially does NOT touch ``s.state`` — the caller commits state
        only on success, so a failed rung leaves the stream resumable."""
        ex = self.cache.video_executor_for(s.pipeline, s.h, s.w, chunk=n,
                                           rows_per_step=rps,
                                           tune=tune,
                                           prefetch_depth=self.prefetch_depth)
        ins = copy_and_stack(s.pipeline, s.inputs, [f.frames for f in frames],
                             (s.h, s.w), n)
        with trace.span("engine.execute", pipeline=s.pipeline, xla=True):
            out, new_state = ex(ins, s.state)
            out.block_until_ready()
        outs = deliver(s.pipeline, out, n)
        return outs, new_state, ex.vmem_bytes + ex.frame_state_bytes

    def _run_frame(self, s: VideoSession, f: VideoFrame,
                   rps: int, tune: bool):
        """Single-frame executor call; same no-state-mutation contract."""
        ex = self.cache.video_executor_for(s.pipeline, s.h, s.w, chunk=None,
                                           rows_per_step=rps,
                                           tune=tune,
                                           prefetch_depth=self.prefetch_depth)
        images = copy_frame(s.pipeline, s.inputs, f.frames, (s.h, s.w))
        with trace.span("engine.execute", pipeline=s.pipeline, xla=True):
            out, new_state = ex(images, s.state)
            out.block_until_ready()
        return [out], new_state, ex.vmem_bytes + ex.frame_state_bytes

    def _reference_serve(self, s: VideoSession, frames: list[VideoFrame]):
        """The ladder's reference rung for a *stateful* stream: replay
        the session's host-side input window plus the new frames through
        the pure-jnp oracle, return the tail outputs and a frame-ring
        state rebuilt from the oracle's end-of-window history. Input
        producers resync bitwise (the rings hold raw past inputs);
        internal temporal producers recompute within reference accuracy.
        """
        dag = self.cache.dag_for(s.pipeline)
        if not dag.is_temporal():
            return self._reference(s.pipeline, frames), dict(s.state), 0
        with trace.span("engine.execute", pipeline=s.pipeline,
                        reference=True):
            videos = {}
            for k in s.inputs:
                seq = [jnp.asarray(x, jnp.float32) for x in s.history[k]]
                seq += [jnp.asarray(f.frames[k], jnp.float32)
                        for f in frames]
                videos[k] = jnp.stack(seq)
            out, hist = execute_reference_video(dag, videos,
                                                return_history=True)
            new_state = self._state_from_history(
                dag.temporal_depths(), hist, s.h, s.w)
            outs = [out[t] for t in range(out.shape[0] - len(frames),
                                          out.shape[0])]
        return outs, new_state, 0

    @staticmethod
    def _state_from_history(depths: dict[str, int], hist: dict,
                            h: int, w: int) -> dict[str, jnp.ndarray]:
        """Frame rings from a reference history: newest-first (matching
        the executor's ring layout), zero-padded up to d-1 when the
        stream is younger than its temporal extent."""
        state = {}
        for p, d in depths.items():
            fr = [jnp.asarray(x, jnp.float32) for x in hist.get(p, [])]
            fr = fr[:d - 1]
            fr += [jnp.zeros((h, w), jnp.float32)] * (d - 1 - len(fr))
            state[p] = (jnp.stack(fr) if fr
                        else jnp.zeros((0, h, w), jnp.float32))
        return state

    def _remember(self, s: VideoSession, frames: list[VideoFrame]) -> None:
        """Append served frames to the session's reference window. Only
        served frames: the window must mirror the effective stream the
        device rings saw, and shed/failed frames never happened to it."""
        if s.history is None:
            return
        for k in s.inputs:
            for f in frames:
                s.history[k].append(np.asarray(f.frames[k], np.float32))

    def _execute_stream(self, s: VideoSession, frames: list[VideoFrame]):
        """Serve ``frames`` (in order) against the session. Returns
        (served, failed, vmem, rps) with served = [(frame, out, rung)]
        and failed = [(frame, error_str)]; session state advances only
        over the served prefix/frames. An executor exception becomes
        structured failures instead of escaping with the frames already
        popped."""
        n = len(frames)
        dag = self.cache.dag_for(s.pipeline)
        rps = rows_per_step_for_tile(s.h, self.rows_per_step)
        chunkable = all(p in s.inputs for p in dag.temporal_depths())
        use_chunk = n == self.chunk and n > 1 and chunkable
        if use_chunk:
            try:
                (outs, new_state, vmem), rung = self._descend(
                    (s.pipeline, "chunk"),
                    lambda tune: self._run_chunk(s, frames, n, rps, tune),
                    lambda: self._reference_serve(s, frames))
            except Exception as e:  # noqa: BLE001 - structured failure
                return [], [(f, repr(e)) for f in frames], 0, rps
            s.state = new_state
            self._remember(s, frames)
            return [(f, o, rung) for f, o in zip(frames, outs)], [], vmem, rps
        served: list = []
        failed: list = []
        vmem = 0
        for i, f in enumerate(frames):
            try:
                (outs, new_state, vm), rung = self._descend(
                    (s.pipeline, "frame"),
                    lambda tune: self._run_frame(s, f, rps, tune),
                    lambda: self._reference_serve(s, [f]))
            except Exception as e:  # noqa: BLE001 - structured failure
                if self.resilience is None:     # strict: the rest fails too
                    failed += [(g, repr(e)) for g in frames[i:]]
                    break
                failed.append((f, repr(e)))
                continue    # state untouched: the stream skips this frame
            s.state = new_state
            vmem = max(vmem, vm)
            self._remember(s, [f])
            served.append((f, outs[0], rung))
        return served, failed, vmem, rps

    # ----------------------------------------------------------------- step
    def step(self) -> list:
        """Serve up to ``chunk`` frames of the neediest stream; flushes
        pending shed outcomes first. Returns a mix of
        CompletedVideoFrame, ShedFrame, and FailedFrame ([] when idle).
        """
        results = self._begin_step()
        live = {sid: s.queue for sid, s in self._sessions.items()}
        sid, frames = assemble_batch(live, self.chunk,
                                     age_of=lambda f: f.submitted_at)
        if not frames:
            return results
        s = self._sessions[sid]
        n = len(frames)
        queue_wait = self._queue_wait(frames)
        with trace.span("engine.step", xla=True, engine="video",
                        pipeline=s.pipeline, stream=sid, n_frames=n,
                        queue_wait_s=queue_wait) as sp:
            t0 = time.perf_counter()
            served, failed, vmem, rps = self._execute_stream(s, frames)
            dt = time.perf_counter() - t0
            sp.set(execute_s=dt, delivered=len(served), failed=len(failed))
        if served:
            self.metrics.observe_batch(s.pipeline, len(served), self.chunk,
                                       dt, vmem, rows_per_step=rps)
            self.metrics.fallback_frames += sum(
                1 for _, _, rung in served if rung != self._primary_rung)
        if failed:
            self.metrics.frames_failed += len(failed)
        now = time.perf_counter()
        now_obs = trace.now()
        for f, out, rung in served:
            idx = s.delivered
            s.delivered += 1
            warm = idx >= s.warmup_frames
            if warm and s.first_warm_at is None:
                s.first_warm_at = now
                self.warmup_latency_s.observe(now - s.opened_at)
            lat, late = self._served(f, now, now_obs)
            results.append(CompletedVideoFrame(
                stream=sid, pipeline=s.pipeline, index=idx, output=out,
                warm=warm, latency_s=lat, rung=rung, deadline_missed=late,
                rid=f.rid))
        for f, err in failed:
            results.append(FailedFrame(
                pipeline=s.pipeline, error=err, stream=sid, rid=f.rid,
                latency_s=now - f.submitted_at))
        return results

    def run(self, streams: Mapping[int, list[Mapping[str, np.ndarray]]]
            ) -> dict[int, list[jnp.ndarray]]:
        """Feed whole streams (respecting backpressure), drain to the end.
        Returns outputs per stream in frame order. ``step()`` serves the
        globally neediest stream, so frames already queued on sessions
        *outside* ``streams`` may complete during the drain; they are
        returned under their own stream id rather than dropped, and only
        the requested streams' queues gate termination. In resilient
        mode, permanently rejected frames are dropped from the feed
        (their structured outcomes are not collected here — drive
        ``submit``/``step`` directly for per-frame accounting)."""
        pending = {sid: list(frames) for sid, frames in streams.items()}
        results: dict[int, list] = {sid: [] for sid in streams}

        def queued(sid: int) -> bool:
            s = self._sessions.get(sid)
            return bool(s and s.queue)

        while any(pending.values()) or any(queued(sid) for sid in streams):
            progressed = False
            for sid, frames in pending.items():
                progressed |= self._offer(
                    frames, lambda f, sid=sid: VideoFrame(sid, f))[0]
            for c in self.step():
                progressed = True
                if isinstance(c, CompletedVideoFrame):
                    results.setdefault(c.stream, []).append(c.output)
            if not progressed:
                time.sleep(0.001)  # rate-limit window: don't spin hot
        return results

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["warmup_latency"] = self.warmup_latency_s.snapshot()
        snap["open_streams"] = len(self._sessions)
        return snap
