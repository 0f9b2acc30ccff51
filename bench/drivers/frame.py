"""Driver for ``FrameEngine`` configurations (spatial pipelines).

Every stream is a client of one engine: its frames go to the pipeline's
one queue, and a step batches whatever is queued there, up to
``max_batch``, padding a partial batch.
"""
from __future__ import annotations

import numpy as np

from repro.imaging import CompletedFrame, FrameEngine, FrameRequest, PlanCache


class Driver:
    def __init__(self, config: dict, streams: int):
        eng = config["engine"]
        if eng.get("mode", "strict") != "strict":
            raise ValueError("only strict mode is driven")
        self.pipeline = config["pipeline"]
        self.h, self.w = config["frame"]["height"], config["frame"]["width"]
        self.cache = PlanCache()
        self.engine = FrameEngine(
            cache=self.cache, max_batch=eng["max_batch"],
            rows_per_step=eng["rows_per_step"],
            prefetch_depth=eng["prefetch_depth"],
            tile_shape=tuple(eng["tile_shape"]),
            max_pending=eng.get("max_pending", 64))
        self.slots = eng["max_batch"]
        self.misordered = 0
        self._rids: dict[int, tuple[int, int]] = {}
        self._next_rid = 0

    def warmup(self, frames: np.ndarray, fills: list[int]) -> None:
        """Run one batch of each fill in ``fills``: compiles the one
        padded executor and the small programs around it."""
        for n in fills:
            for i in range(n):
                self.engine.submit(FrameRequest(rid=-1 - i,
                                                pipeline=self.pipeline,
                                                frames={"in": frames[i]}))
            for c in self.engine.step():
                c.output.block_until_ready()

    def submit(self, stream: int, index: int, frame: np.ndarray) -> bool:
        rid = self._next_rid
        self._next_rid += 1
        self._rids[rid] = (stream, index)
        return self.engine.submit(FrameRequest(rid=rid, pipeline=self.pipeline,
                                               frames={"in": frame})) is True

    def step(self) -> list[tuple[int, int, object]]:
        out = []
        for r in self.engine.step():
            key = self._rids.pop(r.rid, None)
            if key is None:
                self.misordered += 1
                continue
            good = isinstance(r, CompletedFrame) and r.rung == "default"
            out.append((*key, r.output if good else None))
        return out

    @property
    def pending(self) -> int:
        return self.engine.pending

    def counters(self) -> dict:
        m = self.engine.metrics
        return {"frames_completed": m.frames_completed,
                "batches": m.batches, "slots": self.slots}

    def executors(self) -> list:
        return self.cache.executors()

    def close(self) -> None:
        self.engine = self.cache = None
