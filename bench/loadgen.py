"""Traffic: one generator for every mix in ``bench/traffic/*.json``.

A mix is ``{"kind": "closed", "streams": S, "queued": Q}`` — S clients,
each keeping Q frames submitted and not yet served — or ``{"kind":
"open", "cameras": N, "fps": F, "jitter_ms": J}``: N cameras, each
sending a frame every 1/F s, at phases that split the period into N
equal slots (a seeded permutation says which camera takes which slot),
each frame off its slot by a seeded jitter uniform in [-J, J] ms. Every
seed thus gets the same arrivals, in another order, and the same sizes.

What a frame holds is a frame of a host-resident pool of random frames
made from the seed: frame k of stream s is ``pool[(a_s + k b_s) % P]``
with a seeded offset a_s and odd stride b_s, so the reference can
rebuild any stream's history. The frames never go to the device ahead
of time: handing them over is part of what the engines are paid for.
"""
from __future__ import annotations

import dataclasses
import math
import time
from contextlib import nullcontext

import numpy as np

SEED_MASK = (1 << 64) - 1


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """A generator for one purpose of one seed; any int seed, any size."""
    return np.random.default_rng([seed & SEED_MASK, purpose])


def make_pool(seed: int, n: int, h: int, w: int) -> np.ndarray:
    """(n, h, w) float32 frames in [0, 1), on the host."""
    return rng_for(seed, 1).random((n, h, w), dtype=np.float32)


class Traffic:
    def __init__(self, spec: dict, seed: int, pool_size: int):
        self.spec = dict(spec)
        self.kind = spec["kind"]
        if self.kind not in ("closed", "open"):
            raise ValueError(f"traffic kind {self.kind!r}: closed or open")
        self.streams = int(spec["streams"] if self.kind == "closed"
                           else spec["cameras"])
        self.queued = int(spec.get("queued", 0))
        self.pool_size = pool_size
        rng = rng_for(seed, 2)
        self._offset = rng.integers(0, pool_size, self.streams)
        self._stride = 2 * rng.integers(0, max(pool_size // 2, 1),
                                        self.streams) + 1
        self._rng = rng

    def content(self, stream: int, index: int) -> int:
        """Pool index of frame ``index`` of ``stream``."""
        return int((self._offset[stream] + index * self._stride[stream])
                   % self.pool_size)

    def schedule(self, seconds: float) -> list[tuple[float, int, int]]:
        """Open traffic: (due seconds from the window's start, stream,
        index) of every frame due in [0, seconds), by due time."""
        if self.kind != "open":
            raise ValueError("a closed mix has no schedule")
        n, period = self.streams, 1.0 / float(self.spec["fps"])
        jitter = float(self.spec["jitter_ms"]) / 1e3
        slots = np.arange(n) * (period / n)
        phase = slots[self._rng.permutation(n)]
        frames = math.ceil(seconds / period) + 1
        due = (phase[:, None] + np.arange(frames)[None, :] * period
               + self._rng.uniform(-jitter, jitter, (n, frames)))
        due = np.maximum(due, 0.0)
        out = [(float(due[s, k]), s, k) for s in range(n)
               for k in range(frames) if due[s, k] < seconds]
        out.sort()
        return out


@dataclasses.dataclass
class FrameRecord:
    stream: int
    index: int
    due: float                   # perf_counter seconds
    submitted: float
    started: float | None = None  # start of the step() that served it
    done: float | None = None     # end of that step()
    ok: bool = False


def _annotate(trace: bool):
    if not trace:
        return lambda name: nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class Loop:
    """Drives one engine driver with one traffic mix for one window.

    ``on_output(stream, index, output)`` sees every served output;
    ``records`` holds every frame offered, in offer order.
    """

    def __init__(self, driver, traffic: Traffic, pool: np.ndarray,
                 on_output, trace: bool = False):
        self.driver = driver
        self.traffic = traffic
        self.pool = pool
        self.on_output = on_output
        self.ann = _annotate(trace)
        self.records: list[FrameRecord] = []
        self._live: dict[tuple[int, int], FrameRecord] = {}
        self._next = [0] * traffic.streams
        self._in_flight = [0] * traffic.streams
        self.refused = 0
        self.misordered = 0
        self.t_start = self.t_close = self.t_end = 0.0
        self.completed_in_window = 0

    # -- one frame in, one step out
    def _offer(self, stream: int, index: int, due: float) -> None:
        frame = self.pool[self.traffic.content(stream, index)]
        rec = FrameRecord(stream, index, due, time.perf_counter())
        self.records.append(rec)
        if self.driver.submit(stream, index, frame):
            self._live[(stream, index)] = rec
            self._in_flight[stream] += 1
        else:
            self.refused += 1

    def _step(self) -> int:
        started = time.perf_counter()
        with self.ann("bench.step"):
            served = self.driver.step()
        done = time.perf_counter()
        n = 0
        for stream, index, output in served:
            rec = self._live.pop((stream, index), None)
            if rec is None:
                self.misordered += 1
                continue
            self._in_flight[stream] -= 1
            rec.started, rec.done = started, done
            if output is not None:
                rec.ok = True
                n += 1
                self.on_output(stream, index, output)
        return n

    def _top_up(self) -> None:
        with self.ann("bench.submit"):
            for s in range(self.traffic.streams):
                while self._in_flight[s] < self.traffic.queued:
                    k = self._next[s]
                    self._next[s] += 1
                    self._offer(s, k, time.perf_counter())

    # -- the two loops
    def run(self, seconds: float) -> None:
        with self.ann("bench.window"):
            if self.traffic.kind == "closed":
                self._closed(seconds)
            else:
                self._open(seconds)
        self._drain()

    def _closed(self, seconds: float) -> None:
        """Keep every client's queue full; the window closes at the end
        of the first step that ends ``seconds`` after it opened."""
        self.t_start = time.perf_counter()
        t_end = self.t_start + seconds
        while True:
            self._top_up()
            self.completed_in_window += self._step()
            now = time.perf_counter()
            if now >= t_end:
                self.t_close = now
                return

    def _open(self, seconds: float) -> None:
        """Offer each frame at its due time, whatever the engine is
        doing; serve while anything is queued. The window holds every
        frame due in [0, seconds) and closes when the last is served."""
        sched = self.traffic.schedule(seconds)
        self.t_start = t0 = time.perf_counter()
        i, n = 0, len(sched)
        while i < n or self.driver.pending:
            now = time.perf_counter()
            if i < n and t0 + sched[i][0] <= now:
                with self.ann("bench.submit"):
                    while i < n and t0 + sched[i][0] <= now:
                        due, s, k = sched[i]
                        self._offer(s, k, t0 + due)
                        i += 1
            if self.driver.pending:
                self.completed_in_window += self._step()
            elif i < n:
                with self.ann("bench.wait"):
                    _sleep_until(t0 + sched[i][0])
        self.t_close = time.perf_counter()

    def _drain(self) -> None:
        """Serve what is still queued after the window (closed mixes):
        every offered frame is checked, none is counted in the window."""
        while self.driver.pending:
            self._step()
        self.t_end = time.perf_counter()

    @property
    def missing(self) -> int:
        """Frames offered and never served correctly: refused, failed,
        or left undelivered."""
        return sum(not r.ok for r in self.records)


def _sleep_until(t: float) -> None:
    """Sleep to within a millisecond of ``t``, then spin to it."""
    left = t - time.perf_counter()
    if left > 0.0015:
        time.sleep(left - 0.001)
    while time.perf_counter() < t:
        pass
