"""FrameEngine: slot-based continuous batching for stencil pipelines.

The FrameEngine multiplexes independent frame requests over
compiled-plan executors. The paper's accelerator compiles once and then
streams frames; here the compiled artifact (plan + jitted Pallas kernel)
lives in a PlanCache and the engine's job is purely scheduling, on the
serving core it shares with video.VideoEngine (imaging/serving.py):

  * **admission** — per-pipeline bounded FIFOs; a full queue refuses the
    request (backpressure to the caller) instead of growing without bound.
  * **batch assembly** — each ``step()`` picks the pipeline whose head
    request is oldest, then fills up to ``max_batch`` slots with same-shape
    frames from that queue (FIFO, so per-pipeline completion order equals
    submission order). Partial batches run with zero-filled idle slots —
    the executor is compiled once at ``max_batch`` and reused. In strict
    mode, when the batch after the one a step dispatched is full and
    untiled, the step copies and stacks it to the device before waiting
    for the running kernel; the next ``step()`` executes it.
  * **tiling dispatch** — frames no larger than ``tile_shape`` run through
    the batched executor directly; larger frames go through the tiled
    executor one request at a time (each frame's tiles ride the batched
    kernel, so slots stay full either way).

**Resilient mode** (``resilience=ResilienceConfig(...)``) threads the
serving control plane through all three:

  * admission *screens* instead of raising — malformed requests (unknown
    pipeline, missing inputs, bad shape/dtype, NaN pixels) come back as
    structured :class:`~repro.resilience.RejectedFrame` results, rate
    limits apply per pipeline, and saturated queues shed their worst
    resident (lowest priority, most deadline-expired) to admit better
    work;
  * requests carry SLA deadlines on the obs clock; expired work is swept
    out of the queues as ``ShedFrame(reason="deadline")`` at the top of
    each step rather than wasting executor time on a guaranteed miss;
  * execution runs down a fallback ladder — tuned plan → default plan →
    pure-jnp reference — each rung behind a circuit breaker, each
    attempt under the retry policy; a batch that exhausts the ladder is
    delivered as structured :class:`FailedFrame` results, so an executor
    exception can never strand queued work mid-``step``.

With ``resilience=None`` (the default) admission keeps its original
strict raise-at-submit contract; the structured-failure guarantee for
executor exceptions holds in both modes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Mapping

import jax.numpy as jnp
import numpy as np

from repro.obs import trace
from repro.resilience import (FailedFrame, Priority, RejectedFrame,
                              ResilienceConfig, screen_frames)

from .plan_cache import PlanCache
from .serving import (BoundedFifo, Lane, ServingCore, assemble_batch,
                      copy_and_stack, deliver, peek_batch)
from .tiling import execute_tiled, rows_per_step_for_tile


@dataclasses.dataclass
class FrameRequest:
    rid: int
    pipeline: str
    frames: Mapping[str, np.ndarray]      # {input name: (H, W)}
    submitted_at: float = 0.0             # stamped by the engine
    priority: int = Priority.NORMAL       # shed protection class
    deadline_s: float | None = None       # relative SLA; None = config's
    deadline: float | None = None         # absolute (obs clock), stamped

    @property
    def shape(self) -> tuple[int, int]:
        return tuple(next(iter(self.frames.values())).shape)


@dataclasses.dataclass
class _Prepared:
    """A full batch popped, copied and stacked while the previous
    batch's kernel ran; the next ``step()`` executes it. ``error`` holds
    what its copy or stack raised, so the batch fails on its own step."""
    pipeline: str
    reqs: list[FrameRequest]
    inputs: dict | None = None
    error: Exception | None = None


@dataclasses.dataclass
class CompletedFrame:
    rid: int
    pipeline: str
    output: jnp.ndarray
    latency_s: float
    rung: str = "default"                 # ladder rung that served it
    deadline_missed: bool = False


class FrameEngine(ServingCore):
    kind = "frame"

    def __init__(self, cache: PlanCache | None = None,
                 max_batch: int = 4, max_pending: int = 64,
                 tile_shape: tuple[int, int] = (128, 128),
                 rows_per_step: int = 8,
                 prefetch_depth: int = 1,
                 autotune: bool = False,
                 registry=None,
                 resilience: ResilienceConfig | None = None):
        super().__init__(cache, max_pending, rows_per_step, prefetch_depth,
                         autotune, registry, resilience)
        self.max_batch = max_batch
        self.tile_shape = tile_shape
        self._queues: dict[str, BoundedFifo] = {}
        # strict mode: the next batch, made ready under the running one
        self._prepared: _Prepared | None = None

    # ------------------------------------------------------------ admission
    def submit(self, req: FrameRequest) -> bool | RejectedFrame:
        """Enqueue a request. Legacy (strict) mode: False means the
        engine is saturated (retry after draining a step — the
        backpressure contract) and malformed requests raise here, at
        admission, so they can never poison an assembled batch.
        Resilient mode: every refusal — malformed, rate-limited, or
        saturated — returns a falsy :class:`RejectedFrame` carrying the
        reason instead of raising mid-loop."""
        if self.resilience is not None:
            return self._submit_resilient(req)
        dag = self.cache.dag_for(req.pipeline)
        if dag.is_temporal():
            raise ValueError(
                f"request {req.rid}: pipeline {req.pipeline!r} reads frame "
                f"history; serve it through video.VideoEngine")
        needed = set(dag.input_stages())
        if not needed <= set(req.frames):
            raise ValueError(
                f"request {req.rid}: pipeline {req.pipeline!r} needs inputs "
                f"{sorted(needed)}, got {sorted(req.frames)}")
        if len({np.shape(f) for f in req.frames.values()}) != 1:
            raise ValueError(f"request {req.rid}: input frames must share "
                             f"one (H, W) shape")
        return self._push(req, self._queue_for(req.pipeline))

    def _queue_for(self, pipeline: str) -> BoundedFifo:
        q = self._queues.get(pipeline)
        if q is None:
            q = self._queues[pipeline] = BoundedFifo(self.max_pending)
        return q

    def _route(self, req: FrameRequest) -> Lane | RejectedFrame:
        try:
            dag = self.cache.dag_for(req.pipeline)
        except KeyError as e:
            return RejectedFrame("unknown_pipeline", pipeline=req.pipeline,
                                 detail=str(e), rid=req.rid)
        if dag.is_temporal():
            return RejectedFrame("temporal_pipeline", pipeline=req.pipeline,
                                 detail="serve via video.VideoEngine",
                                 rid=req.rid)
        defect = screen_frames(req.frames, set(dag.input_stages()))
        if defect is not None:
            reason, detail = defect
            return RejectedFrame(reason, pipeline=req.pipeline,
                                 detail=detail, rid=req.rid)
        return Lane(self._queue_for(req.pipeline), req.pipeline)

    def _lanes(self):
        return (Lane(q, name) for name, q in self._queues.items())

    @property
    def pending(self) -> int:
        prepared = len(self._prepared.reqs) if self._prepared else 0
        return prepared + super().pending

    # ------------------------------------------------------------ execution
    def _tiled(self, shape: tuple[int, int]) -> bool:
        th, tw = self.tile_shape
        return shape[0] > th or shape[1] > tw

    def _assemble(self, name: str, reqs: list[FrameRequest],
                  prefetched: bool = False) -> dict:
        """Copy a batch's frames to the device and stack them, padded to
        ``max_batch``: the executor's inputs."""
        return copy_and_stack(name, self.cache.dag_for(name).input_stages(),
                              [r.frames for r in reqs], reqs[0].shape,
                              self.max_batch, prefetched=prefetched)

    def _prefetch(self) -> None:
        """Pop the next batch and copy and stack it now, while the batch
        just dispatched runs on the device. Only a full batch that the
        batched executor serves is taken: a partial one may still fill
        up, and the tiled path copies per tile."""
        name, reqs = peek_batch(self._queues, self.max_batch,
                                age_of=lambda r: r.submitted_at,
                                compatible=lambda a, b: a.shape == b.shape)
        if len(reqs) < self.max_batch or self._tiled(reqs[0].shape):
            return
        q = self._queues[name]
        for _ in reqs:
            q.pop()
        self._prepared = prep = _Prepared(name, reqs)
        self.metrics.batches_prefetched += 1
        try:
            prep.inputs = self._assemble(name, reqs, prefetched=True)
        except Exception as e:  # noqa: BLE001 - the batch is popped:
            prep.error = e      # it fails on its own step, not this one

    def _run_compiled(self, name: str, reqs: list[FrameRequest],
                      h: int, w: int, tiled: bool, rps: int, tune: bool,
                      inputs: dict | None = None) -> tuple[list, int]:
        """Run one batch; ``inputs``: the batch already copied and
        stacked (a prepared batch). Strict mode prepares the next full
        batch between dispatch and the wait for this one; resilient
        mode's ladder has to see this batch end before the next
        starts."""
        th, tw = self.tile_shape
        if tiled:
            with trace.span("engine.execute", pipeline=name, xla=True):
                outs = [execute_tiled(self.cache, name, r.frames, th,
                                      tw, batch=self.max_batch,
                                      rows_per_step=rps, tune=tune,
                                      prefetch_depth=self.prefetch_depth)
                        for r in reqs]
                for o in outs:       # sync: dt must measure execution,
                    o.block_until_ready()  # not async dispatch
            return outs, self.cache.vmem_bytes()
        ex = self.cache.executor_for(name, h, w, batch=self.max_batch,
                                     rows_per_step=rps, tune=tune,
                                     prefetch_depth=self.prefetch_depth)
        if inputs is None:
            inputs = self._assemble(name, reqs)
        with trace.span("engine.execute", pipeline=name, xla=True):
            batch_out = ex(inputs)
            if self.resilience is None:
                self._prefetch()
            batch_out.block_until_ready()
        return deliver(name, batch_out, len(reqs)), ex.vmem_bytes

    def _execute(self, name: str, reqs: list[FrameRequest], h: int, w: int,
                 tiled: bool, rps: int, prepared: _Prepared | None = None
                 ) -> tuple[list, int, str]:
        """Run a batch; returns (outputs, vmem_bytes, rung). Resilient
        mode descends the fallback ladder; strict mode runs the primary
        path directly (exceptions propagate to step()'s failure path),
        starting from ``prepared`` inputs when the last step made them,
        and prepares the next full batch while this one runs."""
        if prepared is not None and prepared.error is not None:
            raise prepared.error
        inputs = prepared.inputs if prepared else None
        (outs, vmem), rung = self._descend(
            name,
            lambda tune: self._run_compiled(name, reqs, h, w, tiled, rps,
                                            tune, inputs),
            lambda: (self._reference(name, reqs), 0))
        return outs, vmem, rung

    # ----------------------------------------------------------------- step
    def step(self) -> list:
        """Assemble and execute one batch; flushes pending shed/expiry
        outcomes first. Returns a mix of CompletedFrame, ShedFrame, and
        FailedFrame results ([] when idle)."""
        results = self._begin_step()
        prepared, self._prepared = self._prepared, None
        if prepared is not None:
            name, reqs = prepared.pipeline, prepared.reqs
        else:
            name, reqs = assemble_batch(
                self._queues, self.max_batch,
                age_of=lambda r: r.submitted_at,
                compatible=lambda a, b: a.shape == b.shape)
        if not reqs:
            return results
        queue_wait = self._queue_wait(reqs)
        h, w = reqs[0].shape
        th, tw = self.tile_shape
        tiled = self._tiled((h, w))
        # the row-group factor that actually executes: clamped by the tile
        # height on the tiled path, by the frame height otherwise
        rps = rows_per_step_for_tile(min(th, h) if tiled else h,
                                     self.rows_per_step)
        with trace.span("engine.step", xla=True, engine="frame",
                        pipeline=name, n_frames=len(reqs), tiled=tiled,
                        rows_per_step=rps, queue_wait_s=queue_wait) as sp:
            t0 = time.perf_counter()
            try:
                outs, vmem, rung = self._execute(name, reqs, h, w,
                                                 tiled, rps, prepared)
            except Exception as e:  # noqa: BLE001 - structured failure:
                # the batch is already popped; losing the exception here
                # would strand it, raising would strand the *rest* of
                # the queue — so it travels as FailedFrame results
                err = repr(e)
                self.metrics.frames_failed += len(reqs)
                sp.set(failed=len(reqs), error=type(e).__name__)
                now = time.perf_counter()
                results.extend(FailedFrame(
                    pipeline=name, error=err, rid=r.rid,
                    latency_s=now - r.submitted_at) for r in reqs)
                return results
            dt = time.perf_counter() - t0
            self.metrics.observe_batch(name, len(reqs), self.max_batch, dt,
                                       vmem, rows_per_step=rps)
            if rung != self._primary_rung:
                self.metrics.fallback_frames += len(reqs)
            now = time.perf_counter()
            now_obs = trace.now()
            missed = 0
            for r, out in zip(reqs, outs):
                lat, late = self._served(r, now, now_obs)
                missed += late
                results.append(CompletedFrame(
                    rid=r.rid, pipeline=name, output=out, latency_s=lat,
                    rung=rung, deadline_missed=late))
            sp.set(execute_s=dt, rung=rung, delivered=len(reqs),
                   deadline_missed=missed)
        return results

    def run(self, requests: list[FrameRequest]) -> dict:
        """Submit everything (respecting backpressure), drain to
        completion. Returns {rid: output} for completed requests; in
        resilient mode, rids that ended rejected/shed/failed map to
        their structured outcome object instead."""
        pending = list(requests)
        results: dict = {}
        while pending or self.pending:
            progressed, dropped = self._offer(pending)
            results.update((req.rid, rej) for req, rej in dropped)
            for c in self.step():
                progressed = True
                if isinstance(c, CompletedFrame):
                    results[c.rid] = c.output
                elif c.rid is not None:
                    results[c.rid] = c
            if not progressed:
                time.sleep(0.001)  # rate-limit window: don't spin hot
        return results
