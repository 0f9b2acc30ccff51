"""The serving core under FrameEngine and VideoEngine.

Both engines queue frames behind bounded admission, copy batches to the
device, run them down one fallback ladder and deliver them one way; they
differ in what a queue belongs to (a pipeline or a stream) and in what a
batch may hold. What they share lives here, once:

  * **scheduling primitives** — :class:`BoundedFifo`, the
    oldest-head-first batch choice (:func:`peek_batch`,
    :func:`assemble_batch`) and idle-slot padding (:func:`pad_batch`);
  * **control plane** — :class:`ServingCore`, the base of both engines:
    plan cache, metrics, pending gauge, shed outbox, admission
    controller and fallback ladder; resilient admission (screen, rate
    limit, deadline stamp, shed on overload, push), the expiry sweep,
    the ladder's rungs and its reference rung for a spatial DAG;
  * **transfer path** — :func:`copy_and_stack`, :func:`copy_frame` and
    :func:`deliver`, the one place the ``engine.assemble``,
    ``engine.h2d``, ``engine.stack`` and ``engine.deliver`` spans open.

An engine names itself (``kind``: the span attr and the metric prefix),
screens a request and says which queue it joins (``_route``), and lists
its queues (``_lanes``).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable, Collection, Hashable, Iterable, Mapping

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.stencil_pipeline import unstack
from repro.obs import trace
from repro.resilience import (AdmissionController, FallbackLadder,
                              RejectedFrame, ResilienceConfig, ShedFrame,
                              overdue_s, pick_shed_victim, split_expired)

from .metrics import EngineMetrics
from .plan_cache import PlanCache


# --------------------------------------------------------------- scheduling
class BoundedFifo:
    """FIFO with a hard capacity — ``push`` refuses instead of growing.

    Refusal is the backpressure signal: the caller (client or load
    generator) must retry after draining, which is exactly the behavior a
    streaming accelerator's full input queue presents to its producer.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._q: deque = deque()

    def push(self, item: Any) -> bool:
        if len(self._q) >= self.capacity:
            return False
        self._q.append(item)
        return True

    def pop(self) -> Any:
        return self._q.popleft()

    def peek(self) -> Any:
        return self._q[0]

    def remove(self, item: Any) -> None:
        """Remove a specific resident item (identity match) — the
        shed-on-overload eviction path: the control plane picks a
        victim by priority/deadline, then pulls it out of the middle."""
        for i, it in enumerate(self._q):
            if it is item:
                del self._q[i]
                return
        raise ValueError("item not in queue")

    def drain(self) -> list:
        """Pop everything, FIFO order — cancelling a closed stream's
        queue, or sweeping deadline-expired work for re-filtering."""
        items = list(self._q)
        self._q.clear()
        return items

    def __iter__(self):
        return iter(self._q)

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)


def peek_batch(queues: Mapping[Hashable, BoundedFifo], max_batch: int,
               age_of: Callable[[Any], float],
               compatible: Callable[[Any, Any], bool] | None = None,
               ) -> tuple[Hashable, list]:
    """Oldest-head-first batch choice across per-stream FIFOs, left in
    place.

    Picks the stream whose head item is oldest (per ``age_of``, lower =
    older), then takes up to ``max_batch`` items from its front in FIFO
    order, stopping early when ``compatible(first, item)`` says an item
    cannot share the batch (e.g. mismatched frame shapes must not be
    padded together). Returns (stream_key, items); (None, []) when idle.
    """
    live = [(k, q) for k, q in queues.items() if q]
    if not live:
        return None, []
    key, q = min(live, key=lambda kq: age_of(kq[1].peek()))
    first = q.peek()
    items = [first]
    for item in itertools.islice(q, 1, None):
        if len(items) == max_batch or (
                compatible is not None and not compatible(first, item)):
            break
        items.append(item)
    return key, items


def assemble_batch(queues: Mapping[Hashable, BoundedFifo], max_batch: int,
                   age_of: Callable[[Any], float],
                   compatible: Callable[[Any, Any], bool] | None = None,
                   ) -> tuple[Hashable, list]:
    """Pop the batch :func:`peek_batch` chooses. Returns (stream_key,
    items); (None, []) when idle."""
    key, items = peek_batch(queues, max_batch, age_of, compatible)
    for _ in items:
        queues[key].pop()
    return key, items


def pad_batch(items: list, slots: int, make_idle: Callable[[], Any]) -> list:
    """Fill a partial batch up to ``slots`` with idle entries.

    Slot-based engines compile their executor once at the full batch size
    and run partial batches with idle slots rather than recompiling per
    fill level; this is the one place that padding policy lives. Raises
    if the batch already overflows the slot count — that is an assembly
    bug, not a padding concern.
    """
    if len(items) > slots:
        raise ValueError(f"batch of {len(items)} exceeds {slots} slots")
    return items + [make_idle() for _ in range(slots - len(items))]


# ------------------------------------------------------------------ transfer
def copy_and_stack(pipeline: str, feeds: Collection[str],
                   frames: list[Mapping], shape: tuple[int, int],
                   slots: int, prefetched: bool | None = None) -> dict:
    """Copy a batch's frames to the device and stack each feed, padded
    with zero frames to ``slots``: an executor's inputs. ``prefetched``
    (FrameEngine only) marks the assembly span of a batch copied while
    the one before it ran."""
    h, w = shape
    tag = {} if prefetched is None else {"prefetched": prefetched}
    with trace.span("engine.assemble", pipeline=pipeline, xla=True, **tag):
        with trace.span("engine.h2d", pipeline=pipeline, xla=True,
                        frames=len(frames),
                        bytes=len(frames) * len(feeds) * h * w * 4):
            copies = {n: [jnp.asarray(f[n], jnp.float32) for f in frames]
                      for n in feeds}
        with trace.span("engine.stack", pipeline=pipeline, xla=True):
            return {n: jnp.stack(pad_batch(
                c, slots, lambda: jnp.zeros((h, w), jnp.float32)))
                for n, c in copies.items()}


def copy_frame(pipeline: str, feeds: Collection[str], frame: Mapping,
               shape: tuple[int, int]) -> dict:
    """Copy one frame's feeds to the device, unstacked: the single-frame
    executor's inputs."""
    h, w = shape
    with trace.span("engine.h2d", pipeline=pipeline, xla=True, frames=1,
                    bytes=len(feeds) * h * w * 4):
        return {n: jnp.asarray(frame[n], jnp.float32) for n in feeds}


def deliver(pipeline: str, out, n: int) -> list:
    """Split a batched output into its first ``n`` frames: every padded
    row is split and the live ones kept, so one program per frame shape
    serves every fill."""
    with trace.span("engine.deliver", pipeline=pipeline, xla=True,
                    frames=n, programs=1):
        return list(unstack(out)[:n])


# ------------------------------------------------------------- control plane
@dataclasses.dataclass(frozen=True)
class Lane:
    """One queue of admitted work and whose it is: a pipeline's
    (FrameEngine) or a stream's (VideoEngine)."""
    queue: BoundedFifo
    pipeline: str
    stream: int | None = None

    @property
    def key(self) -> Hashable:
        """The rate limiter's bucket: the stream, else the pipeline."""
        return self.pipeline if self.stream is None else self.stream


class ServingCore:
    """What FrameEngine and VideoEngine share: the plan cache, metrics
    and resilience plumbing, admission, the expiry sweep and the
    fallback ladder. A subclass sets ``kind`` and supplies ``_route``
    and ``_lanes``."""

    kind = ""            # "frame" / "video": span attr, metric prefix

    def __init__(self, cache: PlanCache | None, max_pending: int,
                 rows_per_step: int, prefetch_depth: int, autotune: bool,
                 registry, resilience: ResilienceConfig | None):
        # ``registry``: a shared obs.MetricsRegistry for the serving
        # telemetry plane; default = a private one per engine. A cache
        # constructed here joins the same registry.
        self.cache = cache if cache is not None else \
            PlanCache(registry=registry,
                      retry=resilience.retry if resilience else None)
        self.max_pending = max_pending
        # row-group blocking factor for every executor this engine
        # compiles; clamped per batch so frames shorter than R still run
        self.rows_per_step = rows_per_step
        # DMA/compute overlap depth for every executor this engine
        # compiles (1 = synchronous BlockSpec streaming)
        self.prefetch_depth = prefetch_depth
        # opt-in: serve with the cache's autotuned memory config (one
        # memoized design-space search per (pipeline, width))
        self.autotune = autotune
        self.resilience = resilience
        prefix = f"{self.kind}_engine"
        self.metrics = EngineMetrics(registry=registry, prefix=prefix)
        # live queue depth for the telemetry plane: spans only show work
        # that *ran*; the collector needs the standing backlog as a gauge
        self._pending_gauge = self.metrics.registry.gauge(
            f"{prefix}_pending_frames",
            help="frames admitted but not yet served")
        # shed outcomes produced at admission time (overload evictions)
        # or by the expiry sweep; flushed into the next step()'s results
        self._shed_outbox: list[ShedFrame] = []
        self._admission = self._ladder = None
        if resilience is not None:
            self._admission = AdmissionController(
                resilience.rate, resilience.burst, clock=trace.now)
            self._ladder = FallbackLadder(
                retry=resilience.retry,
                failure_threshold=resilience.breaker_failures,
                reset_after_s=resilience.breaker_reset_s,
                on_retry=lambda a, d, e: self.metrics.observe_retry(d))

    # ------------------------------------------------------- engine hooks
    def _route(self, item) -> Lane | RejectedFrame:
        """Resilient mode's screen: the lane ``item`` joins, or why it
        is refused."""
        raise NotImplementedError

    def _lanes(self) -> Iterable[Lane]:
        """Every queue of admitted work."""
        raise NotImplementedError

    @property
    def pending(self) -> int:
        return sum(len(lane.queue) for lane in self._lanes())

    @property
    def _primary_rung(self) -> str:
        return "tuned" if self.autotune else "default"

    # ---------------------------------------------------------- admission
    def _push(self, item, queue: BoundedFifo) -> bool:
        """Strict admission, once the engine's checks passed: stamp and
        enqueue; False = the queue is full (backpressure)."""
        item.submitted_at = time.perf_counter()
        self.metrics.frames_offered += 1
        ok = queue.push(item)
        if ok:
            self.metrics.frames_submitted += 1
        else:
            self.metrics.frames_rejected += 1
        return ok

    def _reject(self, rej: RejectedFrame) -> RejectedFrame:
        self.metrics.frames_rejected += 1
        with trace.span("resilience.reject", engine=self.kind,
                        pipeline=rej.pipeline or "?", reason=rej.reason,
                        retryable=rej.retryable):
            pass
        return rej

    def _shed(self, item, reason: str, now: float, lane: Lane) -> None:
        self.metrics.frames_shed += 1
        od = overdue_s(item.deadline, now)
        self._shed_outbox.append(ShedFrame(
            reason=reason, pipeline=lane.pipeline,
            priority=int(item.priority), rid=item.rid, stream=lane.stream,
            deadline=item.deadline,
            overdue_s=od if od > float("-inf") else 0.0))
        tag = {} if lane.stream is None else {"stream": lane.stream}
        with trace.span("resilience.shed", engine=self.kind,
                        pipeline=lane.pipeline, reason=reason,
                        priority=int(item.priority), **tag):
            pass

    def _submit_resilient(self, item) -> bool | RejectedFrame:
        """Screen, rate limit, stamp the deadline, shed on overload,
        push. Every refusal is a falsy RejectedFrame."""
        self.metrics.frames_offered += 1
        lane = self._route(item)
        if isinstance(lane, RejectedFrame):
            return self._reject(lane)
        # a refusal names the request (FrameEngine) or the stream
        tag = {"rid": item.rid} if lane.stream is None \
            else {"stream": lane.stream}
        if not self._admission.allow(lane.key):
            return self._reject(RejectedFrame(
                "rate_limited", pipeline=lane.pipeline, retryable=True,
                **tag))
        cfg = self.resilience
        now = trace.now()
        item.submitted_at = time.perf_counter()
        dl = item.deadline_s if item.deadline_s is not None \
            else cfg.default_deadline_s
        item.deadline = (now + dl) if dl is not None else None
        q = lane.queue
        if len(q) >= q.capacity and cfg.shed_on_overload:
            # within one stream every frame shares the session priority,
            # so there eviction only ever claims an expired resident —
            # live-video frame dropping, never reordering
            victim = pick_shed_victim(
                q, int(item.priority), now,
                priority_of=lambda r: int(r.priority),
                deadline_of=lambda r: r.deadline,
                age_of=lambda r: r.submitted_at)
            if victim is not None:
                q.remove(victim)
                self._shed(victim, "overload", now, lane)
        if not q.push(item):
            return self._reject(RejectedFrame(
                "saturated", pipeline=lane.pipeline, retryable=True,
                **tag))
        self.metrics.frames_submitted += 1
        return True

    def _sweep_expired(self) -> None:
        """Drop queued work whose deadline already passed — executing it
        would burn capacity on a guaranteed SLA miss."""
        now = trace.now()
        for lane in self._lanes():
            if not lane.queue:
                continue
            live, expired = split_expired(lane.queue.drain(), now,
                                          lambda r: r.deadline)
            for r in live:
                lane.queue.push(r)
            for r in expired:
                self._shed(r, "deadline", now, lane)

    def _offer(self, pending: list,
               make: Callable[[Any], Any] = lambda item: item
               ) -> tuple[bool, list]:
        """Submit the head of ``pending`` (as ``make(head)``) until a
        refusal that may pass later: backpressure or a rate limit. A
        permanent refusal drops its item. Returns (progressed,
        [(item, RejectedFrame)] dropped)."""
        progressed, dropped = False, []
        while pending:
            r = self.submit(make(pending[0]))
            if isinstance(r, RejectedFrame) and not r.retryable:
                dropped.append((pending[0], r))
            elif r is not True:
                break
            pending.pop(0)
            progressed = True
        return progressed, dropped

    # ----------------------------------------------------------- stepping
    def _begin_step(self) -> list:
        """A step's first results: the expiry sweep's and the admission
        sheds' outcomes. Also updates the pending gauge."""
        results: list = []
        if self.resilience is not None and self.resilience.shed_expired:
            self._sweep_expired()
        if self._shed_outbox:
            results, self._shed_outbox = self._shed_outbox, []
        self._pending_gauge.set(self.pending)
        return results

    def _queue_wait(self, items: list) -> float:
        """How long the oldest of ``items`` sat admitted but unserved —
        the "where did the 40 ms go" term the executor time can never
        explain. Recorded and returned."""
        wait = time.perf_counter() - min(it.submitted_at for it in items)
        self.metrics.observe_queue_wait(wait)
        return wait

    def _served(self, item, now: float, now_obs: float
                ) -> tuple[float, bool]:
        """Record a served item's latency and deadline; returns
        (latency_s, deadline missed)."""
        lat = now - item.submitted_at
        self.metrics.observe_latency(lat)
        late = item.deadline is not None and now_obs > item.deadline
        if late:
            self.metrics.observe_deadline_miss(now_obs - item.deadline)
        return lat, late

    def _descend(self, key: Hashable, compiled: Callable[[bool], Any],
                 reference: Callable[[], Any]) -> tuple[Any, str]:
        """Run the fallback ladder under ``key``: tuned (when autotuning)
        → default → the reference (when enabled); ``compiled(tune)`` is
        the compiled path. Strict mode runs the primary rung alone and
        lets its exception propagate. Returns (result, rung)."""
        if self.resilience is None:
            return compiled(self.autotune), self._primary_rung
        rungs = []
        if self.autotune:
            rungs.append(("tuned", lambda: compiled(True)))
        rungs.append(("default", lambda: compiled(False)))
        if self.resilience.reference_fallback:
            rungs.append(("reference", reference))
        return self._ladder.run(key, rungs)

    def _reference(self, pipeline: str, items: list) -> list:
        """The ladder's last rung for a spatial DAG: the pure-jnp oracle,
        frame by frame. Slow — no line buffers, no fused kernel — but it
        has no plan, no executor, and no cache to fail, so it bounds the
        blast radius of every compiled-path fault at "degraded
        throughput"."""
        dag = self.cache.dag_for(pipeline)
        with trace.span("engine.execute", pipeline=pipeline, reference=True):
            outs = [ref.stencil_pipeline_ref(
                dag, {n: jnp.asarray(it.frames[n], jnp.float32)
                      for n in dag.input_stages()}) for it in items]
            for o in outs:
                o.block_until_ready()
        return outs

    def snapshot(self) -> dict:
        """Engine + cache telemetry in one dict (the serving plane's
        JSON view; the Prometheus view is metrics.registry)."""
        snap = self.metrics.snapshot()
        snap["pending"] = self.pending
        snap["cache"] = self.cache.snapshot()
        return snap
