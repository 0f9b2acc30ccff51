"""Driver for ``VideoEngine`` configurations (temporal pipelines).

Every stream is a stream of the engine with its own frame rings on the
device. A step serves the stream whose head frame waited longest: a
full chunk in one call of the chunk executor, fewer frames one call of
the single-frame executor each.
"""
from __future__ import annotations

import numpy as np

from repro.imaging import PlanCache
from repro.video import CompletedVideoFrame, VideoEngine, VideoFrame


class Driver:
    def __init__(self, config: dict, streams: int):
        eng = config["engine"]
        if eng.get("mode", "strict") != "strict":
            raise ValueError("only strict mode is driven")
        self.pipeline = config["pipeline"]
        self.h, self.w = config["frame"]["height"], config["frame"]["width"]
        self.cache = PlanCache()
        self.engine = VideoEngine(
            cache=self.cache, chunk=eng["chunk"],
            rows_per_step=eng["rows_per_step"],
            prefetch_depth=eng["prefetch_depth"],
            max_pending=eng.get("max_pending", 64))
        self.slots = eng["chunk"]
        self.misordered = 0
        self._sids = [self.engine.open_stream(self.pipeline, self.h, self.w)
                      for _ in range(streams)]
        self._stream_of = {sid: s for s, sid in enumerate(self._sids)}
        # the stream position the engine must give each accepted frame
        self._accepted = [0] * streams
        self._position: dict[tuple[int, int], int] = {}

    def warmup(self, frames: np.ndarray, fills: list[int]) -> None:
        """Serve one step of each fill on a stream of its own, then
        close it: a full chunk compiles the chunk executor, a smaller
        fill the single-frame one."""
        sid = self.engine.open_stream(self.pipeline, self.h, self.w)
        for n in fills:
            for i in range(n):
                self.engine.submit(VideoFrame(sid, {"in": frames[i]}))
            for c in self.engine.step():
                c.output.block_until_ready()
        self.engine.close_stream(sid)

    def submit(self, stream: int, index: int, frame: np.ndarray) -> bool:
        ok = self.engine.submit(VideoFrame(self._sids[stream], {"in": frame},
                                           rid=index)) is True
        if ok:
            self._position[(stream, index)] = self._accepted[stream]
            self._accepted[stream] += 1
        return ok

    def step(self) -> list[tuple[int, int, object]]:
        out = []
        for r in self.engine.step():
            stream = self._stream_of.get(r.stream)
            if stream is None or r.rid is None:
                self.misordered += 1
                continue
            good = (isinstance(r, CompletedVideoFrame)
                    and r.rung == "default")
            if good and r.index != self._position.pop((stream, r.rid), None):
                self.misordered += 1     # delivered out of stream order
                good = False
            out.append((stream, r.rid, r.output if good else None))
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
        self._sids = []
